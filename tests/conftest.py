"""Test harness: force an 8-device virtual CPU mesh before any backend init.

Multi-chip TPU hardware is not available in CI; sharding tests run on a
virtual CPU mesh via ``--xla_force_host_platform_device_count=8`` (SURVEY.md
§4's multi-device test strategy).

``jax.config.update`` pins the platform as well, in case anything imported
jax before this conftest ran (the backend initializes lazily, so this takes
effect as long as no test module touched a device at import time).
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax

jax.config.update("jax_platforms", "cpu")
