"""Backend compiles inside the measured window; every program the window
runs should have been compiled or loaded from the cache in set-up, so this
reads 0. Counted from JAX's ``/jax/core/compile/backend_compile_duration``
monitoring event."""

LAYER = "driver"
UNIT = "count"
MOVES = "analysis_s"
SOURCE = "program_counter"


def read(obs):
    return float(obs.compiles)
