"""Share of the finish's roofline: the least time for the top-k
eigenvectors of the centred G (``benchmark/work.py:finish_least``), per
chip, over the device time of the finish executables in the trace
averaged over the chips, per analysis."""

from benchmark.work import finish_least

LAYER = "finish"
UNIT = "%"
MOVES = "analysis_s"
SOURCE = "device_trace"

# Today's jit names of the finish: the fused single-dispatch finish and
# the dense-eigh one.
KERNELS = ("_finish_jit", "_pcoa_jit")


def _least(obs):
    return finish_least(obs.work["n"], obs.work["k"], obs.peaks, obs.chips)


def read(obs):
    from benchmark.trace import module_seconds

    if obs.trace is None or not obs.analyses:
        return None
    seconds = module_seconds(obs.trace, KERNELS) / obs.analyses
    if seconds <= 0.0:
        return None
    return 100.0 * _least(obs)["seconds"] / seconds


def describe(obs):
    least = _least(obs)
    return (f"least time {least['seconds']:.6g} s per analysis, "
            f"{least['bound']} bound ({least['ops']:.6g} ops, "
            f"{least['bytes']:.6g} bytes)")
