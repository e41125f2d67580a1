"""Share of the finish's roofline on a mesh: the least time for the
top-k eigenvectors of the centred G (``benchmark/work.py:finish_least``,
per chip), over the device time of the mesh finish's programs (centring
and the subspace eigensolve) in the trace averaged over the chips, per
analysis."""

from benchmark.work import finish_least

LAYER = "finish"
UNIT = "%"
MOVES = "analysis_s"
SOURCE = "device_trace"

# The jit names of the mesh finish: the centring, and the fixed and the
# adaptive subspace eigensolve.
KERNELS = ("mesh_center", "mesh_subspace_eig")


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
    from benchmark.trace import module_seconds

    least = _least(obs)
    device = (module_seconds(obs.trace, KERNELS) / obs.analyses
              if obs.trace is not None and obs.analyses else 0.0)
    return (f"least time {least['seconds']:.6g} s per analysis, "
            f"{least['bound']} bound ({least['ops']:.6g} ops, "
            f"{least['bytes']:.6g} bytes); finish programs "
            f"{device:.6g} s per analysis per chip")
