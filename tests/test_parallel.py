"""Sharding tests on the virtual 8-device CPU mesh (SURVEY.md §4)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from spark_examples_tpu.ops import gramian, double_center, principal_components
from spark_examples_tpu.parallel import (
    gramian_variant_parallel,
    make_mesh,
    sharded_gramian_blockwise,
    sharded_pcoa,
    topk_eig_randomized,
)


@pytest.fixture
def x_small():
    rng = np.random.default_rng(0)
    return (rng.random((32, 256)) < 0.3).astype(np.int8)


class TestMesh:
    def test_default_mesh_all_devices(self):
        mesh = make_mesh()
        assert mesh.devices.size == 8
        assert mesh.axis_names == ("data",)

    def test_spec_mesh(self):
        mesh = make_mesh("data:4,model:2")
        assert mesh.shape == {"data": 4, "model": 2}

    def test_oversized_spec_rejected(self):
        with pytest.raises(ValueError, match="needs 16"):
            make_mesh("data:16")


class TestShardedGramian:
    def test_variant_parallel_matches_dense(self, x_small):
        mesh = make_mesh("data:8")
        g = gramian_variant_parallel(jnp.asarray(x_small), mesh)
        np.testing.assert_array_equal(
            np.asarray(g), np.asarray(gramian(x_small))
        )

    def test_blockwise_sharded_matches_dense_1d(self, x_small):
        mesh = make_mesh("data:8")
        blocks = [x_small[:, i : i + 64] for i in range(0, 256, 64)]
        g = sharded_gramian_blockwise(blocks, 32, mesh)
        np.testing.assert_array_equal(
            np.asarray(g), np.asarray(gramian(x_small))
        )

    def test_blockwise_sharded_matches_dense_2d(self, x_small):
        mesh = make_mesh("data:4,model:2")
        blocks = [x_small[:, i : i + 64] for i in range(0, 256, 64)]
        g = sharded_gramian_blockwise(blocks, 32, mesh)
        # G must actually be laid out across the mesh.
        assert len(g.sharding.device_set) == 8
        np.testing.assert_array_equal(
            np.asarray(g), np.asarray(gramian(x_small))
        )

    def test_blockwise_sharded_packed_bit_identical(self, x_small):
        """The bit-packed feed (the production default in the model) must
        be bit-identical to the unpacked sharded path, including a block
        width (100) that is neither a multiple of 8 nor of the mesh's
        variant-axis divisor — pad bytes unpack to inert zero columns."""
        mesh = make_mesh("data:4,model:2")
        ragged = [x_small[:, :100], x_small[:, 100:200], x_small[:, 200:]]
        want = np.asarray(gramian(x_small))
        got = sharded_gramian_blockwise(ragged, 32, mesh, packed=True)
        assert len(got.sharding.device_set) == 8
        np.testing.assert_array_equal(np.asarray(got), want)


class TestShardedEig:
    def test_randomized_topk_matches_eigh(self):
        rng = np.random.default_rng(5)
        q, _ = np.linalg.qr(rng.random((64, 64)))
        w = np.linspace(50, 0.01, 64) * np.sign(rng.random(64) - 0.2)
        c = (q * w) @ q.T
        c = np.asarray(double_center(c), dtype=np.float32)

        exact_v, exact_w = principal_components(c, 3)
        rand_v, rand_w = topk_eig_randomized(jnp.asarray(c), 3, iters=60)
        np.testing.assert_allclose(
            np.asarray(rand_w), np.asarray(exact_w), rtol=1e-3
        )
        np.testing.assert_allclose(
            np.abs(np.asarray(rand_v)), np.abs(np.asarray(exact_v)), atol=1e-3
        )

    def test_sharded_pcoa_dense_path(self, x_small):
        mesh = make_mesh("data:4,model:2")
        blocks = [x_small[:, i : i + 64] for i in range(0, 256, 64)]
        g = sharded_gramian_blockwise(blocks, 32, mesh)
        coords, w = sharded_pcoa(g, 2, mesh)
        golden, _ = principal_components(
            np.asarray(double_center(np.asarray(gramian(x_small)))), 2
        )
        np.testing.assert_allclose(
            np.asarray(coords), np.asarray(golden), atol=1e-4
        )

    def test_sharded_pcoa_randomized_path(self, x_small):
        mesh = make_mesh("data:4,model:2")
        g = gramian(x_small)
        g = jax.device_put(
            g, NamedSharding(mesh, P("data", "model"))
        )
        coords, w = sharded_pcoa(g, 2, mesh, dense_eigh_limit=8)
        golden, _ = principal_components(
            np.asarray(double_center(np.asarray(gramian(x_small)))), 2
        )
        np.testing.assert_allclose(
            np.abs(np.asarray(coords)), np.abs(golden), atol=1e-2
        )


class TestDriverWithMesh:
    def test_pca_driver_sharded(self):
        from spark_examples_tpu.genomics.fixtures import (
            DEFAULT_VARIANT_SET_ID,
            synthetic_cohort,
        )
        from spark_examples_tpu.models.pca import VariantsPcaDriver
        from spark_examples_tpu.utils.config import PcaConfig

        conf = PcaConfig(
            variant_set_ids=[DEFAULT_VARIANT_SET_ID], block_variants=64
        )
        mesh = make_mesh("data:4,model:2")
        source = synthetic_cohort(24, 200)
        result = VariantsPcaDriver(conf, source, mesh=mesh).run()

        conf2 = PcaConfig(variant_set_ids=[DEFAULT_VARIANT_SET_ID])
        unsharded = VariantsPcaDriver(
            conf2, synthetic_cohort(24, 200)
        ).run()
        a = np.array([r[1:] for r in result])
        b = np.array([r[1:] for r in unsharded])
        np.testing.assert_allclose(a, b, atol=1e-4)


class TestReviewRegressions:
    def test_sharded_gramian_nondivisible_n(self):
        """N=23 on an 8-way mesh: padding must make the mesh path work for
        arbitrary cohort sizes."""
        rng = np.random.default_rng(9)
        x = (rng.random((23, 128)) < 0.3).astype(np.int8)
        mesh = make_mesh("data:4,model:2")
        blocks = [x[:, i : i + 32] for i in range(0, 128, 32)]
        g = sharded_gramian_blockwise(blocks, 23, mesh)
        assert g.shape == (23, 23)
        np.testing.assert_array_equal(np.asarray(g), np.asarray(gramian(x)))

    def test_sharded_gramian_float_blocks_compute_in_float(self):
        """Out-of-trace dtype resolution must key off the block's REAL
        dtype: a fractional float block (imputed dosages) computes its
        exact f32 product, never a silent int8 truncation (round-4
        review finding on the resolve hoist)."""
        mesh = make_mesh("data:4,model:2")
        xb = np.full((8, 16), 0.5, np.float32)
        g = sharded_gramian_blockwise([xb], 8, mesh)
        np.testing.assert_allclose(
            np.asarray(g), np.full((8, 8), 4.0, np.float32)
        )

    def test_driver_mesh_uses_sharded_pcoa_nondivisible(self):
        from spark_examples_tpu.genomics.fixtures import (
            DEFAULT_VARIANT_SET_ID,
            synthetic_cohort,
        )
        from spark_examples_tpu.models.pca import VariantsPcaDriver
        from spark_examples_tpu.utils.config import PcaConfig

        conf = PcaConfig(
            variant_set_ids=[DEFAULT_VARIANT_SET_ID], block_variants=64
        )
        mesh = make_mesh("data:8")
        result = VariantsPcaDriver(
            conf, synthetic_cohort(23, 150), mesh=mesh
        ).run()
        assert len(result) == 23

    def test_randomized_meets_parity_bar_on_realistic_spectrum(self):
        """Population-structure cohorts (the real workload) converge far
        below the 1e-4 parity bar — the basis for trusting the randomized
        path at N where dense eigh is infeasible."""
        rng = np.random.default_rng(0)
        n, v = 1024, 8192
        groups = rng.integers(0, 3, size=n)
        af = rng.beta(0.4, 1.2, size=(3, v))
        x = (rng.random((n, v)) < af[groups]).astype(np.int8)
        c = np.asarray(
            double_center(np.asarray(gramian(x), np.float64))
        ).astype(np.float32)

        exact_v, _ = principal_components(c.astype(np.float64), 2)
        rand_v, _ = topk_eig_randomized(jnp.asarray(c), 2, iters=15)
        err = np.abs(
            np.abs(np.asarray(rand_v)) - np.abs(np.asarray(exact_v))
        ).max()
        assert err < 1e-4, err


class TestAdaptiveEig:
    """Opt-in tol-based convergence for the randomized eig path."""

    @staticmethod
    def _structured_c(n=1024, v=8192, seed=0):
        rng = np.random.default_rng(seed)
        groups = rng.integers(0, 3, size=n)
        af = rng.beta(0.4, 1.2, size=(3, v))
        x = (rng.random((n, v)) < af[groups]).astype(np.int8)
        return np.asarray(
            double_center(np.asarray(gramian(x), np.float64))
        ).astype(np.float32)

    def test_tol_zero_bit_identical_to_fixed(self):
        """With an unreachable tol and the cap a chunk multiple, the
        adaptive path applies the exact same operation sequence as the
        fixed sweep — bit-identical output."""
        c = jnp.asarray(self._structured_c(n=256, v=2048))
        fixed_v, fixed_w = topk_eig_randomized(c, 2, iters=20)
        adapt_v, adapt_w = topk_eig_randomized(
            c, 2, iters=20, tol=0.0, check_every=5
        )
        np.testing.assert_array_equal(
            np.asarray(fixed_v), np.asarray(adapt_v)
        )
        np.testing.assert_array_equal(
            np.asarray(fixed_w), np.asarray(adapt_w)
        )

    def test_converges_early_and_meets_parity_bar(self):
        """On a sharp population-structure spectrum the adaptive sweep
        stops well before the cap and still clears the 1e-4 bar."""
        from spark_examples_tpu.utils.tracing import StageTimer

        c = self._structured_c()
        exact_v, _ = principal_components(c.astype(np.float64), 2)
        timer = StageTimer()
        rand_v, _ = topk_eig_randomized(
            jnp.asarray(c), 2, iters=60, tol=1e-6, timer=timer
        )
        err = np.abs(
            np.abs(np.asarray(rand_v)) - np.abs(np.asarray(exact_v))
        ).max()
        assert err < 1e-4, err
        note = [
            n
            for notes in timer.notes.values()
            for n in notes
            if "randomized eig" in n
        ]
        assert len(note) == 1
        used = int(note[0].split(":")[1].split("/")[0])
        assert used < 60  # converged before the cap

    def test_sharded_pcoa_threads_eig_tol(self):
        """eig_tol flows through sharded_pcoa's randomized branch: a
        tolerance float32 iteration reaches is met, one below its floor
        raises rather than return an unconverged answer.

        Structured spectrum (population groups): the randomized path is
        rotation-fragile on flat random spectra by design — the same
        reason test_sharded_pcoa_randomized_path compares at 1e-2.
        """
        from spark_examples_tpu.parallel.sharded import (
            EigNotConvergedError,
        )

        rng = np.random.default_rng(3)
        groups = rng.integers(0, 3, size=96)
        af = rng.beta(0.4, 1.2, size=(3, 2048))
        x = (rng.random((96, 2048)) < af[groups]).astype(np.int8)
        g = np.asarray(gramian(x), np.float32)
        mesh = make_mesh()
        exact, _ = sharded_pcoa(
            jnp.asarray(g), 2, mesh, dense_eigh_limit=1024
        )
        approx, _ = sharded_pcoa(
            jnp.asarray(g),
            2,
            mesh,
            dense_eigh_limit=8,  # force the randomized branch
            eig_tol=1e-6,
        )
        err = np.abs(
            np.abs(np.asarray(approx)) - np.abs(np.asarray(exact))
        ).max()
        assert err < 1e-4, err
        with pytest.raises(EigNotConvergedError, match="1e-09"):
            sharded_pcoa(
                jnp.asarray(g), 2, mesh, dense_eigh_limit=8, eig_tol=1e-9
            )


def test_cli_pca_with_mesh_flag(capsys, tmp_path):
    from spark_examples_tpu.cli.main import main

    rc = main(
        [
            "pca",
            "--fixture-samples",
            "13",
            "--fixture-variants",
            "90",
            "--mesh-shape",
            "data:4,model:2",
            "--output-path",
            str(tmp_path / "mesh"),
        ]
    )
    assert rc == 0
    assert "Matrix size: 13" in capsys.readouterr().out
    assert (tmp_path / "mesh-pca.tsv").exists()


class TestSpectralGapWarning:
    """Flat spectra must be loud, not silently unstable (round-2 verdict:
    a weakly structured cohort gets a rotation-ambiguous PC2 from dense
    eigh and randomized eig alike — detect it at runtime)."""

    @staticmethod
    def _matrix_with_spectrum(w, seed=3):
        rng = np.random.default_rng(seed)
        q, _ = np.linalg.qr(rng.random((len(w), len(w))))
        return ((q * w) @ q.T).astype(np.float32)

    def test_degenerate_gap_warns(self):
        from spark_examples_tpu.parallel import SpectralGapWarning

        c = self._matrix_with_spectrum(
            np.array([10.0, 5.0, 4.999] + [0.01] * 29)
        )
        with pytest.warns(SpectralGapWarning, match=r"\|λ3\|/\|λ2\|"):
            topk_eig_randomized(jnp.asarray(c), 2, iters=40)

    def test_separated_gap_silent(self):
        import warnings

        from spark_examples_tpu.parallel import SpectralGapWarning

        c = self._matrix_with_spectrum(
            np.array([10.0, 5.0, 1.0] + [0.01] * 29)
        )
        with warnings.catch_warnings():
            warnings.simplefilter("error", SpectralGapWarning)
            topk_eig_randomized(jnp.asarray(c), 2, iters=40)

    def test_gap_ratio_lands_in_stage_report(self):
        from spark_examples_tpu.utils.tracing import StageTimer

        timer = StageTimer()
        c = self._matrix_with_spectrum(
            np.array([10.0, 5.0, 1.0] + [0.01] * 29)
        )
        with timer.stage("pca"):
            topk_eig_randomized(jnp.asarray(c), 2, iters=40, timer=timer)
        report = timer.report()
        assert "spectral gap" in report
        assert "0.2" in timer.notes["pca"][0]  # |λ3|/|λ2| = 1/5

    def test_dense_paths_also_detect_degeneracy(self):
        """The dense-eigh branches (sharded_pcoa small-N, the default
        single-host pcoa) must be as loud on a flat spectrum as the
        randomized path — review finding round 3."""
        from spark_examples_tpu.ops.pcoa import check_spectral_gap
        from spark_examples_tpu.parallel import SpectralGapWarning

        # Build the near-degenerate pair INSIDE the centering-invariant
        # subspace (eigvecs ⊥ 1), so double_center leaves the flat gap
        # intact on the way into the dense branch.
        rng = np.random.default_rng(3)
        a = rng.random((32, 32))
        a -= a.mean(axis=0, keepdims=True)  # columns ⊥ ones
        q, _ = np.linalg.qr(a)
        w = np.array([10.0, 5.0, 4.999] + [0.01] * 28)
        c = ((q[:, :31] * w) @ q[:, :31].T).astype(np.float32)

        mesh = make_mesh("data:4,model:2")
        g = jax.device_put(c, NamedSharding(mesh, P("data", "model")))
        with pytest.warns(SpectralGapWarning):
            sharded_pcoa(g, 2, mesh)  # n=32 <= limit: dense branch

        vecs, vals = principal_components(jnp.asarray(c), 3)
        with pytest.warns(SpectralGapWarning):
            check_spectral_gap(np.asarray(vals), 2)


def test_ring_reduction_matches_psum():
    from spark_examples_tpu.parallel import gramian_variant_parallel_ring

    rng = np.random.default_rng(21)
    x = (rng.random((16, 256)) < 0.3).astype(np.int8)
    mesh = make_mesh("data:8")
    ring = np.asarray(gramian_variant_parallel_ring(jnp.asarray(x), mesh))
    psum = np.asarray(gramian_variant_parallel(jnp.asarray(x), mesh))
    np.testing.assert_array_equal(ring, psum)
    np.testing.assert_array_equal(ring, np.asarray(gramian(x)))

    # Float-valued X (dosages): replicas must still be bitwise canonical.
    xf = rng.random((16, 256)).astype(np.float32)
    ringf = gramian_variant_parallel_ring(jnp.asarray(xf), mesh)
    shards = [np.asarray(s.data) for s in ringf.addressable_shards]
    for sh in shards[1:]:
        np.testing.assert_array_equal(shards[0], sh)
