"""Share of the Gramian's roofline on a mesh: the least time the chips
need for the cohort's G (``benchmark/work.py:gramian_least``, per chip),
over the device time of the mesh tile programs in the trace averaged
over the chips, per analysis."""

from benchmark.work import gramian_least

LAYER = "Gramian"
UNIT = "%"
MOVES = "analysis_s"
SOURCE = "device_trace"

# The jit names of the host-local mesh accumulator's tile programs: the
# int8 MXU tile update, the tile scatter (scan or Pallas kernel) and the
# dense-route tile step.
KERNELS = ("mesh_tile_mxu", "mesh_tile_scatter", "mesh_tile_dense")


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
    from benchmark.trace import module_seconds

    least = _least(obs)
    device = (module_seconds(obs.trace, KERNELS) / obs.analyses
              if obs.trace is not None and obs.analyses else 0.0)
    return (f"least time {least['seconds']:.6g} s per analysis, "
            f"{least['bound']} bound ({least['ops']:.6g} ops, "
            f"{least['bytes']:.6g} bytes); tile programs "
            f"{device:.6g} s per analysis per chip")
