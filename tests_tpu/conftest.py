"""On-chip certifications: these tests run only where JAX's default
backend is a TPU and skip everywhere else.

The main ``tests/`` suite forces an 8-device virtual CPU mesh and can
never certify that the TPU's compiled programs run bit-exact on the
real chip. Run this directory on the chip through the chip tool
(``python -m pytest tests_tpu/ -q``); on a CPU-only machine every test
skips from the ``tpu`` fixture. jax imports stay inside fixtures and test
bodies, so collection never initializes a backend.
"""

import pytest


@pytest.fixture(scope="session")
def tpu():
    import jax

    if jax.default_backend() != "tpu":
        pytest.skip("no TPU backend on this machine")
    from spark_examples_tpu.utils.compile_cache import (
        enable_persistent_cache,
    )

    enable_persistent_cache()
    return jax
