"""The main path's TPU programs compile for a described v5e chip.

No chip is attached here: the TPU compiler compiles for a topology that
is only described (``jax.experimental.topologies``), which catches what
the CPU backend cannot — an operation the TPU compiler refuses, a
program that does not fit a chip's memory.
Nothing runs, so these tests say nothing about results or times.

The topology is described only inside the module fixture below (never at
import time, in a ``skipif`` or in ``parametrize``): only one process may
load the TPU library, and the test-worker that collects this file is the
one that loads it. The persistent compilation cache is off around these
compiles — an entry written for a described chip cannot be read back
without one.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def topo():
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        from jax.experimental import topologies

        try:
            yield topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2"
            )
        except Exception as e:  # noqa: BLE001 — any failure means "cannot"
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_fused_finish_compiles_at_north_star(one_chip):
    """The fused finish program (centering → panel-rank check →
    CholeskyQR subspace eig → packed readback) at N=2504, k=2 with the
    shipped sweep defaults."""
    from spark_examples_tpu.ops.fused import (
        _DEF_ITERS,
        _DEF_OVERSAMPLE,
        _finish_jit,
    )

    compiled = _finish_jit.lower(
        _spec((2504, 2504), jnp.float32, one_chip),
        2,
        _DEF_OVERSAMPLE,
        _DEF_ITERS,
        _spec((2,), jnp.uint32, one_chip),
    ).compile()
    # ≥: the TPU layout pads the minor dimension to its tiling.
    mem = compiled.memory_analysis()
    assert mem.output_size_in_bytes >= 2504 * (2 + _DEF_OVERSAMPLE + 4) * 4


def test_packed_int8_gramian_update_compiles(one_chip):
    """One packed block update ``G += unpack(Xp) · unpack(Xp)ᵀ`` on the
    integer MXU at (2504 samples, 8192 variants)."""
    from spark_examples_tpu.ops.gramian import _gramian_accumulate_packed_jit

    compiled = _gramian_accumulate_packed_jit.lower(
        _spec((2504, 2504), jnp.float32, one_chip),
        _spec((2504, 8192 // 8), jnp.uint8, one_chip),
        n_bits=8192,
        compute_dtype=np.dtype(np.int8),
    ).compile()
    assert compiled.memory_analysis().output_size_in_bytes >= 2504 * 2504 * 4


def test_scatter_route_mxu_update_compiles(one_chip):
    """The scatter route's MXU update at a rare-variant window of
    (3,202 samples, 8,192 carrier rows, bucket 64): the carrier-count
    panel built on the device, then the int8 contraction."""
    from spark_examples_tpu.ops.sparse import _scatter_accumulate_jit

    compiled = _scatter_accumulate_jit.lower(
        _spec((3202, 3202), jnp.float32, one_chip),
        _spec((8192, 64), jnp.int32, one_chip),
        path="mxu",
        compute_dtype=jnp.int8,
    ).compile()
    assert "convolution" in compiled.as_text()
    assert compiled.memory_analysis().output_size_in_bytes >= 3202 * 3202 * 4


def test_scatter_built_update_compiles_at_bucket_512(one_chip):
    """A single-device window above bucket 127: the panel scatter-built
    from a carrier list of 2^20 entries over 8,192 sites of a
    (4,096, 4,096) G, then the int8 contraction."""
    from spark_examples_tpu.ops.sparse import _scatter_accumulate_jit

    compiled = _scatter_accumulate_jit.lower(
        _spec((4096, 4096), jnp.float32, one_chip),
        _spec((2, 1 << 20), jnp.int32, one_chip),
        path="mxu-tile",
        compute_dtype=jnp.int8,
        n_cols=8192,
    ).compile()
    assert "convolution" in compiled.as_text()
    assert compiled.memory_analysis().output_size_in_bytes >= 4096 * 4096 * 4


def _mesh_2x2(topo):
    from jax.sharding import Mesh

    return Mesh(np.array(topo.devices[:4]).reshape(2, 2), ("data", "model"))


def test_mesh_tile_mxu_update_compiles_at_ukb50k(topo):
    """The int8 MXU tile update of the host-local mesh stream at the
    ukb50k_exome_rare.mesh cell's shape: a 49,960^2 f32 G in 24,980^2
    tiles on the 2x2 mesh, one 8,192-site window's carrier list of
    262,144 entries. Each chip's program holds its tile, the two int8
    panels and the contraction's transients within one chip's 16 GB."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from spark_examples_tpu.parallel.sharded import _sparse_tile_kernels

    mesh = _mesh_2x2(topo)
    update = _sparse_tile_kernels(
        mesh, "data", "model", 49960, 24980, 24980, "float32", "int8",
    )[4]
    compiled = update.lower(
        _spec((49960, 49960), jnp.float32,
              NamedSharding(mesh, P("data", "model"))),
        _spec((2, 262144), jnp.int32, NamedSharding(mesh, P(None, None))),
        n_cols=8192,
    ).compile()
    assert "convolution" in compiled.as_text()
    mem = compiled.memory_analysis()
    tile = 24980 * 24980 * 4
    assert mem.output_size_in_bytes >= tile
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < 16e9


def test_pod_mirror_tile_update_compiles_on_2x2_mesh(topo):
    """A pod step's int8 MXU tile update under the mirror partition:
    panels scatter-built from a gathered (16,384, 512) carrier matrix
    into (2,048, 2,048) tiles of an N = 4,096 G on the 2x2 mesh."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from spark_examples_tpu.parallel.sharded import _sparse_tile_kernels

    mesh = _mesh_2x2(topo)
    update = _sparse_tile_kernels(
        mesh, "data", "model", 4096, 2048, 2048, "float32", "int8", True,
    )[4]
    compiled = update.lower(
        _spec((4096, 4096), jnp.float32,
              NamedSharding(mesh, P("data", "model"))),
        _spec((16384, 512), jnp.int32, NamedSharding(mesh, P(None, None))),
    ).compile()
    assert "convolution" in compiled.as_text()
    assert compiled.memory_analysis().output_size_in_bytes >= 2048 * 2048 * 4


def test_mesh_finish_compiles_at_ukb50k(topo):
    """The mesh finish's centring and adaptive subspace eigensolve at
    N = 49,960 on the 2x2 mesh: per chip one 24,980^2 tile of the centred
    matrix besides G's, within one chip's 16 GB."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from spark_examples_tpu.ops.pcoa import randomized_panel_width
    from spark_examples_tpu.parallel.sharded import (
        MESH_EIG_CHECK_EVERY,
        MESH_EIG_MAX_ITERS,
        mesh_center,
        mesh_subspace_eig_adaptive,
    )

    mesh = _mesh_2x2(topo)
    tiled = NamedSharding(mesh, P("data", "model"))
    center = mesh_center.lower(
        _spec((49960, 49960), jnp.float32, tiled)).compile()
    mem = center.memory_analysis()
    assert mem.temp_size_in_bytes + 2 * 24980 * 24980 * 4 < 16e9
    p = randomized_panel_width(49960, 2)
    eig = mesh_subspace_eig_adaptive.lower(
        _spec((49960, 49960), jnp.float32, tiled),
        _spec((49960, p), jnp.float32, NamedSharding(mesh, P(None, None))),
        1e-5, k=2, max_iters=MESH_EIG_MAX_ITERS, chunk=MESH_EIG_CHECK_EVERY,
    ).compile()
    mem = eig.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < 16e9
