"""On-chip certifications: Gramian dtype-path agreement and PCoA parity.

Every import of jax (and of modules that import it) stays inside test
bodies/fixtures: at COLLECTION time nothing may initialize a backend
(tests_tpu/conftest.py).
"""

import numpy as np
import pytest


def _random_blocks(n, v, density=0.1, seed=0):
    rng = np.random.default_rng(seed)
    return (rng.random((n, v)) < density).astype(np.int8)


class TestNumericsOnHardware:
    def test_int8_and_f32_gramians_agree(self, tpu):
        """Every dtype mode is exact for 0/1 data below 2^24; the chip's
        integer-MXU path (the production default — 1.8× over f32 in the
        round-3 mode probe) must agree with forced-f32 bit-for-bit.
        The hand-written Pallas kernels this class once certified were
        deleted after losing to the XLA einsum ~10× end-to-end on this
        same chip (ops/gramian.py module docstring)."""
        import jax.numpy as jnp

        from spark_examples_tpu.ops import gramian_blockwise

        n, v = 512, 4096
        blocks = [_random_blocks(n, v, seed=s) for s in (2, 3)]
        f32 = np.asarray(
            gramian_blockwise(blocks, n, compute_dtype=jnp.float32)
        )
        auto = np.asarray(gramian_blockwise(blocks, n))  # int8 MXU path
        i8 = np.asarray(
            gramian_blockwise(
                blocks, n, compute_dtype=jnp.int8, accum_dtype=jnp.int32
            )
        )
        np.testing.assert_array_equal(f32, auto)
        np.testing.assert_array_equal(f32, i8.astype(f32.dtype))

    def test_pcoa_parity_vs_mllib_reference(self, tpu):
        """The BASELINE parity bar (≤1e-4 vs MLlib semantics), certified
        on the chip rather than the CPU stand-in."""
        from spark_examples_tpu.ops import (
            gramian_blockwise,
            mllib_principal_components_reference,
            pcoa,
        )

        n, v = 512, 8192
        blocks = [_random_blocks(n, v, seed=7)]
        g = gramian_blockwise(blocks, n)
        coords = np.asarray(pcoa(g, 2)[0])
        # Both paths sign-normalize deterministically, so coordinates
        # compare directly (same idiom as the CPU parity tests).
        want, _ = mllib_principal_components_reference(
            np.asarray(g).astype(np.float64), 2
        )
        np.testing.assert_allclose(coords, want, atol=1e-4)


def _structured_blocks(n, v, block_v, seed=0):
    """Population-structure cohort split into fixed-width blocks (the
    convergence regime every randomized-eig parity bar assumes)."""
    rng = np.random.default_rng(seed)
    pop = rng.integers(0, 3, n)
    base = rng.random(v) * 0.12
    shift = (rng.random((3, v)) < 0.15) * rng.random((3, v)) * 0.5
    prob = np.clip(base[None, :] + shift[pop], 0, 0.9)
    x = (rng.random((n, v)) < prob).astype(np.int8)
    return [x[:, i : i + block_v] for i in range(0, v, block_v)]


class TestProductionDefaultsOnHardware:
    """Round-5 breadth (verdict ask #5): certify every default the
    shipped ``run()`` can take ON CHIP, under the host-readback barrier
    discipline (``utils/sync.py``)."""

    def test_packed_transfer_bit_identity(self, tpu):
        """The production default feed (bit-packed host→device transfer,
        8x fewer bytes) must be BIT-IDENTICAL to the unpacked path on the
        real chip — pad bits unpack to inert zero columns."""
        from spark_examples_tpu.ops import gramian_blockwise
        from spark_examples_tpu.utils.sync import host_sync

        n, v = 512, 4096
        blocks = [_random_blocks(n, v, seed=s) for s in (4, 5)]
        unpacked = gramian_blockwise(blocks, n)
        packed = gramian_blockwise(blocks, n, packed=True)
        host_sync((unpacked, packed))
        np.testing.assert_array_equal(
            np.asarray(unpacked), np.asarray(packed)
        )

    def test_fused_finish_matches_dense_pcoa_on_chip(self, tpu):
        """The shipped default PCA route (--pca-mode auto → fused
        streaming accumulate + single-dispatch CholeskyQR finish) vs the
        dense-eigh route, on chip, at the product parity bar."""
        from spark_examples_tpu.ops import gramian_blockwise, pcoa
        from spark_examples_tpu.ops.fused import pcoa_fused_blocks
        from spark_examples_tpu.utils.sync import host_sync

        n, v = 512, 8192
        blocks = _structured_blocks(n, v, 2048, seed=11)
        coords, vals, row_sums = pcoa_fused_blocks(blocks, n, 2)
        g = gramian_blockwise(blocks, n, packed=True)
        host_sync(g)
        want = np.asarray(pcoa(g, 2)[0])
        assert np.abs(coords - want).max() <= 1e-4
        # Row sums ride the same packed readback as the coordinates;
        # they feed the "Non zero rows" parity print.
        np.testing.assert_allclose(
            row_sums, np.asarray(g).sum(axis=1), rtol=1e-6
        )

    def test_randomized_adaptive_eig_vs_dense_at_4096(self, tpu):
        """The stress-regime eig (randomized subspace iteration, fixed
        and adaptive --eig-tol) vs dense eigh at N=4096 on chip — the
        crossover scale where the product switches routes."""
        import jax.numpy as jnp

        from spark_examples_tpu.ops import gramian_blockwise, pcoa
        from spark_examples_tpu.ops.centering import double_center
        from spark_examples_tpu.parallel.sharded import topk_eig_randomized
        from spark_examples_tpu.utils.sync import host_sync

        n, v = 4096, 8192
        blocks = _structured_blocks(n, v, 4096, seed=13)
        g = gramian_blockwise(blocks, n, packed=True)
        host_sync(g)
        dense = np.asarray(pcoa(g, 2)[0])
        c = double_center(jnp.asarray(g))
        fixed_vecs, _ = topk_eig_randomized(c, 2, iters=30, seed=0)
        assert np.abs(np.asarray(fixed_vecs) - dense).max() <= 1e-4
        adaptive_vecs, _ = topk_eig_randomized(
            c, 2, iters=60, tol=1e-6, seed=0
        )
        assert np.abs(np.asarray(adaptive_vecs) - dense).max() <= 1e-4

    def test_sharded_gramian_program_on_chip(self, tpu):
        """The sharded-Gramian program (shard_map accumulate, packed
        feed, GSPMD layout) executes on REAL TPU hardware. This chip is
        single-device, so the mesh is 1-wide — the multi-device
        geometry itself is certified on the 8-device virtual mesh
        (tests/test_parallel.py) and by the driver's dryrun_multichip;
        what only hardware can certify is that the sharded program
        compiles and runs on the TPU toolchain, which this does."""
        import jax
        from jax.sharding import Mesh

        from spark_examples_tpu.ops import gramian_blockwise
        from spark_examples_tpu.parallel.mesh import DATA_AXIS
        from spark_examples_tpu.parallel.sharded import (
            sharded_gramian_blockwise,
        )
        from spark_examples_tpu.utils.sync import host_sync

        n, v = 256, 2048
        blocks = [_random_blocks(n, v, seed=17)]
        mesh = Mesh(np.array(jax.devices()[:1]), (DATA_AXIS,))
        sharded = sharded_gramian_blockwise(blocks, n, mesh, packed=True)
        plain = gramian_blockwise(blocks, n, packed=True)
        host_sync((sharded, plain))
        np.testing.assert_array_equal(
            np.asarray(sharded), np.asarray(plain)
        )
