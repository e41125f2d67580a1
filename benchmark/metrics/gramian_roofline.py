"""Share of the Gramian's roofline: the least time the chip needs for the
cohort's G (``benchmark/work.py:gramian_least``, the same count whatever
route or kernel does the work), per chip, over the device time of the
accumulate executables in the trace averaged over the chips, per
analysis."""

from benchmark.work import gramian_least

LAYER = "Gramian"
UNIT = "%"
MOVES = "analysis_s"
SOURCE = "device_trace"

# Today's jit names of every Gramian accumulation step: the packed int8
# MXU step, the unpacked one, and the scatter step (scan or Pallas kernel).
KERNELS = (
    "_gramian_accumulate_packed_jit",
    "_gramian_accumulate_jit",
    "_scatter_accumulate_jit",
)


def _least(obs):
    w = obs.work
    return gramian_least(w["n"], w["v"], w["nnz"], w["sum_k2"], obs.peaks,
                         obs.chips)


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
