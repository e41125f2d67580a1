"""Sparse-aware, mesh-sharded Gramian suite (ROADMAP item 2).

Pins the biobank-scale path end to end: the OOB-drop scatter kernel is
bit-identical to the dense integer-exact reference across mesh shapes
(1×1, 2×1, 2×2 host-device meshes), shuffled window orders, and density
edge cases; the per-window dense/sparse switch; the per-host
sample-range ingest contract; the streaming-sparse footprint bound that
replaced the round-4 verdict's 16·N² host refusal; the centralized
k+1-values panel convention; and the ``--pca-mode sparse`` CLI route
with schema-valid telemetry. The N=65536 acceptance run is the ``slow``
test at the bottom.
"""

import json
import os
import sys

import numpy as np
import pytest

import jax.numpy as jnp

from spark_examples_tpu.arrays.blocks import (
    csr_windows,
    restrict_window_to_sample_range,
)
from spark_examples_tpu.genomics.fixtures import (
    DEFAULT_VARIANT_SET_ID,
    synthetic_cohort,
)
from spark_examples_tpu.models.pca import VariantsPcaDriver
from spark_examples_tpu.ops.gramian import gramian
from spark_examples_tpu.ops.pcoa import randomized_panel_width
from spark_examples_tpu.ops.sparse import (
    padded_carrier_matrix,
    sparse_gramian_accumulate,
    sparse_gramian_blockwise,
    window_density,
    window_route,
)
from spark_examples_tpu.parallel.mesh import make_mesh
from spark_examples_tpu.parallel.sharded import (
    sample_bounds_of_indices,
    sparse_sharded_gramian_blockwise,
    topk_eig_randomized,
)
from spark_examples_tpu.utils.config import PcaConfig

sys.path.insert(
    0,
    os.path.join(os.path.dirname(os.path.dirname(__file__)), "scripts"),
)
import validate_trace as validate  # noqa: E402

# The issue's mesh matrix: 1x1, 2x1, 2x2 host-device meshes, plus a
# wider 4x2 when the device count allows. The conftest forces 8 virtual
# CPU devices by default but KEEPS a pre-set
# --xla_force_host_platform_device_count (the CI mesh leg pins 4), so
# the spec list adapts to what is actually available.
import jax  # noqa: E402  (after conftest has pinned the platform)

MESH_SPECS = tuple(
    spec
    for spec, need in (
        ("data:1", 1),
        ("data:2", 2),
        ("data:2,model:2", 4),
        ("data:4,model:2", 8),
    )
    if need <= jax.device_count()
)


def cohort_csr(n, v, density=0.08, seed=0):
    """(x, (indices, offsets)) — a dense reference and its CSR twin."""
    rng = np.random.default_rng(seed)
    x = (rng.random((n, v)) < density).astype(np.int8)
    cols, rows = np.nonzero(x.T)
    lens = np.bincount(cols, minlength=v)
    offsets = np.zeros(v + 1, np.int64)
    np.cumsum(lens, out=offsets[1:])
    return x, (rows.astype(np.int64), offsets)


class TestCarrierMatrix:
    def test_shapes_sentinel_and_values(self):
        idx = np.array([5, 7, 2, 9, 9, 9], dtype=np.int64)
        lens = np.array([2, 1, 0, 3], dtype=np.int64)
        mat = padded_carrier_matrix(idx, lens, sentinel=10)
        assert mat.shape == (4, 8)  # min bucket 8
        assert mat.dtype == np.int32
        np.testing.assert_array_equal(mat[0, :2], [5, 7])
        np.testing.assert_array_equal(mat[1, :1], [2])
        assert (mat[2] == 10).all()  # empty variant: all sentinel
        np.testing.assert_array_equal(mat[3, :3], [9, 9, 9])
        # every pad cell is the sentinel
        assert (mat[0, 2:] == 10).all() and (mat[1, 1:] == 10).all()

    def test_row_padding_and_bucketing(self):
        idx = np.arange(9, dtype=np.int64)
        lens = np.array([9], dtype=np.int64)
        mat = padded_carrier_matrix(idx, lens, sentinel=99, n_rows=4)
        assert mat.shape == (4, 16)  # 9 carriers -> 16 bucket
        assert (mat[1:] == 99).all()  # padded variant rows inert

    def test_n_rows_too_small_rejected(self):
        with pytest.raises(ValueError, match="n_rows"):
            padded_carrier_matrix(
                np.zeros(0, np.int64),
                np.zeros(3, np.int64),
                sentinel=1,
                n_rows=2,
            )


class TestDensityRouting:
    def test_density_and_route_boundary(self):
        # 4 carriers over N=10, V=2 -> density exactly 0.2
        lens = np.array([3, 1])
        assert window_density(lens, 10) == pytest.approx(0.2)
        # Exactly AT the threshold routes dense (the MXU side of the
        # tie) — the boundary the auto selector is pinned to.
        assert window_route(lens, 10, 0.2) == "dense"
        # Just past it: mean density clears, and so does the max
        # per-variant carrier fraction (3/10 < 0.31) -> scatter.
        assert window_route(lens, 10, 0.31) == "scatter"
        assert window_route(np.zeros(4, np.int64), 10, 0.2) == "scatter"
        assert window_density(np.zeros(0, np.int64), 10) == 0.0

    def test_one_common_variant_forces_dense_route(self):
        """Scatter cost scales with k_max², not mean density: ONE
        common variant (k/N past the threshold) buried in an
        otherwise-rare window must route the window dense even though
        its MEAN density whispers 'sparse'."""
        n = 1000
        lens = np.concatenate([[250], np.ones(99, np.int64)])
        assert window_density(lens, n) < 0.02  # mean says sparse...
        assert window_route(lens, n, 0.02) == "dense"  # ...max says no
        # The same window with the common variant removed scatters.
        assert window_route(lens[1:], n, 0.02) == "scatter"

    def test_route_counters_record_the_mix(self):
        from spark_examples_tpu import obs

        reg = obs.get_registry()
        counter = reg.counter(
            "sparse_gramian_windows_total",
            "CSR windows accumulated by the sparse-aware Gramian engine",
        )
        before = {
            r: counter.labels(route=r).value for r in ("scatter", "dense")
        }
        x, pair = cohort_csr(24, 64, density=0.1, seed=4)
        # Threshold splits the stream: sparse windows scatter, the rest
        # densify — and the counters see exactly one window each way.
        g = sparse_gramian_blockwise(
            csr_windows(iter([pair]), 32),
            24,
            density_threshold=window_density(
                np.diff(pair[1][:33]), 24
            ),
            block_variants=32,
        )
        after = {
            r: counter.labels(route=r).value for r in ("scatter", "dense")
        }
        assert after["scatter"] + after["dense"] == (
            before["scatter"] + before["dense"] + 2
        )
        np.testing.assert_array_equal(np.asarray(g), np.asarray(gramian(x)))


class TestBitIdentity:
    def test_meshless_sparse_matches_dense(self):
        x, pair = cohort_csr(37, 300, density=0.08)
        g = sparse_gramian_blockwise(
            csr_windows(iter([pair]), 64), 37, block_variants=64
        )
        np.testing.assert_array_equal(np.asarray(g), np.asarray(gramian(x)))

    def test_meshless_mixed_routes_match_dense(self):
        # Threshold inside the density range -> some windows scatter,
        # some densify; the mix must still be bit-identical.
        x, pair = cohort_csr(37, 300, density=0.08, seed=2)
        g = sparse_gramian_blockwise(
            csr_windows(iter([pair]), 64),
            37,
            density_threshold=0.08,
            block_variants=64,
        )
        np.testing.assert_array_equal(np.asarray(g), np.asarray(gramian(x)))

    @pytest.mark.parametrize("spec", MESH_SPECS)
    def test_sharded_sparse_matches_dense_across_mesh_shapes(self, spec):
        x, pair = cohort_csr(37, 300, density=0.06, seed=1)
        mesh = make_mesh(spec)
        g = sparse_sharded_gramian_blockwise(
            csr_windows(iter([pair]), 64), 37, mesh, block_variants=64
        )
        np.testing.assert_array_equal(np.asarray(g), np.asarray(gramian(x)))

    def test_sharded_shuffled_window_order_bit_identical(self):
        x, pair = cohort_csr(41, 256, density=0.05, seed=7)
        mesh = make_mesh("data:2,model:2")
        windows = list(csr_windows(iter([pair]), 32))
        assert len(windows) >= 4
        rng = np.random.default_rng(3)
        shuffled = [windows[i] for i in rng.permutation(len(windows))]
        a = sparse_sharded_gramian_blockwise(
            iter(windows), 41, mesh, block_variants=32
        )
        b = sparse_sharded_gramian_blockwise(
            iter(shuffled), 41, mesh, block_variants=32
        )
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        np.testing.assert_array_equal(np.asarray(a), np.asarray(gramian(x)))

    def test_density_edge_cases(self):
        n = 19
        # all-zero window, single-nnz row, and a window exactly at the
        # switch threshold — every edge accumulates bit-identically.
        zero_w = (np.zeros(0, np.int64), np.zeros(8, np.int64))
        single = (np.array([4], np.int64), np.array([1], np.int64))
        # 8 variants x n samples: one carrier per variant => density
        # 8/(19*8) — pick the threshold exactly there.
        at_idx = np.arange(8, dtype=np.int64)
        at_lens = np.ones(8, np.int64)
        thr = window_density(at_lens, n)
        windows = [zero_w, single, (at_idx, at_lens)]
        want = np.zeros((n, n), np.float32)
        want[4, 4] += 1
        want[np.arange(8), np.arange(8)] += 1
        for mesh in (None, make_mesh("data:2,model:2")):
            if mesh is None:
                g = sparse_gramian_blockwise(
                    iter(windows), n, density_threshold=thr,
                    block_variants=8,
                )
            else:
                g = sparse_sharded_gramian_blockwise(
                    iter(windows), n, mesh, density_threshold=thr,
                    block_variants=8,
                )
            np.testing.assert_array_equal(np.asarray(g), want)

    def test_empty_stream_yields_zero_g(self):
        g = sparse_gramian_blockwise(iter(()), 5)
        np.testing.assert_array_equal(
            np.asarray(g), np.zeros((5, 5), np.float32)
        )

    def test_out_of_range_carrier_fails_loudly(self):
        bad = (np.array([7], np.int64), np.array([1], np.int64))
        with pytest.raises(ValueError, match="out of range"):
            sparse_gramian_blockwise(iter([bad]), 5)

    def test_scatter_kernel_accumulates_duplicate_pairs(self):
        # Two variants with the same carrier pair in ONE window: the
        # scatter must apply both +1s (XLA scatter-add dup semantics).
        g = jnp.zeros((6, 6), jnp.float32)
        g = sparse_gramian_accumulate(
            g,
            np.array([1, 3, 1, 3], np.int64),
            np.array([2, 2], np.int64),
        )
        assert np.asarray(g)[1, 3] == 2.0 and np.asarray(g)[3, 1] == 2.0


class TestShardedFootprint:
    def test_no_device_holds_nxn(self):
        n = 64
        x, pair = cohort_csr(n, 128, density=0.05, seed=5)
        mesh = make_mesh("data:2,model:2")
        g = sparse_sharded_gramian_blockwise(
            csr_windows(iter([pair]), 64), n, mesh, block_variants=64
        )
        shapes = {s.data.shape for s in g.addressable_shards}
        assert shapes == {(32, 32)}, (
            "each device must hold exactly one (N/rows, N/cols) tile"
        )
        np.testing.assert_array_equal(np.asarray(g), np.asarray(gramian(x)))


class TestSampleRangeContract:
    def test_restrict_window_drops_and_recounts(self):
        idx = np.array([0, 5, 9, 3, 7], np.int64)
        lens = np.array([3, 0, 2], np.int64)
        out_idx, out_lens = restrict_window_to_sample_range(
            idx, lens, 3, 8
        )
        np.testing.assert_array_equal(out_idx, [5, 3, 7])
        np.testing.assert_array_equal(out_lens, [1, 0, 2])

    def test_full_range_is_identity(self):
        idx = np.array([2, 4], np.int64)
        lens = np.array([2], np.int64)
        out_idx, out_lens = restrict_window_to_sample_range(
            idx, lens, 0, 100
        )
        np.testing.assert_array_equal(out_idx, idx)
        np.testing.assert_array_equal(out_lens, lens)

    def test_sample_bounds_of_indices_union(self):
        slices = [
            (slice(32, 64), slice(0, 16)),
            (slice(32, 64), slice(16, 32)),
        ]
        assert sample_bounds_of_indices(slices, 64) == (0, 64)
        assert sample_bounds_of_indices(
            [(slice(8, 16), slice(8, 16))], 64
        ) == (8, 16)
        # Degenerate/empty tile sets fall back to the full range.
        assert sample_bounds_of_indices([], 64) == (0, 64)

    def test_restricted_ingest_is_bit_identical_for_owned_tiles(self):
        """Dropping carriers outside a host's sample-range bounds can
        never change the tiles it owns — the ingest contract that lets
        each mesh host pull only its sample rows (ARCHITECTURE.md)."""
        n = 48
        x, pair = cohort_csr(n, 96, density=0.06, seed=8)
        windows = list(csr_windows(iter([pair]), 32))
        lo, hi = 16, 48  # a fictional host owning tile rows/cols 16..48
        restricted = [
            restrict_window_to_sample_range(i, l, lo, hi)
            for i, l in windows
        ]
        full = np.asarray(gramian(x))
        got = np.asarray(
            sparse_gramian_blockwise(iter(restricted), n, block_variants=32)
        )
        np.testing.assert_array_equal(
            got[lo:hi, lo:hi], full[lo:hi, lo:hi]
        )


class TestDriverSparseMode:
    def _driver(self, mode="sparse", mesh_spec=None, n=30, v=200, **kw):
        conf = PcaConfig(
            variant_set_ids=[DEFAULT_VARIANT_SET_ID],
            block_variants=64,
            pca_mode=mode,
            **kw,
        )
        mesh = make_mesh(mesh_spec) if mesh_spec else None
        source = synthetic_cohort(n, v, population_structure=2, seed=3)
        return VariantsPcaDriver(conf, source, mesh=mesh)

    def test_sparse_mode_matches_stream_coordinates(self):
        sparse = self._driver("sparse").run()
        stream = self._driver("stream").run()
        a = np.array([r[1:] for r in sparse])
        b = np.array([r[1:] for r in stream])
        assert np.abs(a - b).max() <= 1e-4
        assert [r[0] for r in sparse] == [r[0] for r in stream]

    def test_sparse_mode_on_mesh_matches_stream(self):
        sparse = self._driver("sparse", "data:2,model:2").run()
        stream = self._driver("stream").run()
        a = np.array([r[1:] for r in sparse])
        b = np.array([r[1:] for r in stream])
        assert np.abs(a - b).max() <= 1e-4

    def test_sparse_gramian_bit_identical_to_dense_tiers(self):
        d_sparse = self._driver("sparse")
        d_dense = self._driver("stream")
        g_sparse = np.asarray(d_sparse.ingest_gramian())
        g_dense = np.asarray(d_dense.ingest_gramian())
        np.testing.assert_array_equal(g_sparse, g_dense)

    def test_auto_selects_sparse_only_on_sample_sharded_mesh(self):
        auto_mesh = self._driver(
            "auto", "data:2,model:2", sample_shard_threshold=8
        )
        assert auto_mesh._sparse_selected()  # N=30 > 8, host-local mesh
        assert not self._driver(
            "auto", sample_shard_threshold=8
        )._sparse_selected()  # meshless auto keeps the dense tiers
        assert not self._driver(
            "auto", "data:2,model:2"
        )._sparse_selected()  # below the shard threshold
        assert self._driver("sparse")._sparse_selected()  # forced
        assert not self._driver("stream")._sparse_selected()

    def test_auto_sparse_run_matches_dense(self):
        auto = self._driver(
            "auto", "data:2,model:2", sample_shard_threshold=8
        )
        assert auto._sparse_selected()
        a = np.array([r[1:] for r in auto.run()])
        b = np.array([r[1:] for r in self._driver("stream").run()])
        assert np.abs(a - b).max() <= 1e-4

    def test_sparse_rejects_checkpointing_before_ingest(self):
        with pytest.raises(ValueError, match="sparse"):
            self._driver("sparse", checkpoint_dir="/tmp/nope")

    def test_negative_threshold_rejected(self):
        with pytest.raises(ValueError, match="sparse-density-threshold"):
            self._driver("sparse", sparse_density_threshold=-0.1)

    def test_negative_pipeline_depth_rejected(self):
        with pytest.raises(ValueError, match="pod-pipeline-depth"):
            self._driver("sparse", pod_pipeline_depth=-1)

    def test_negative_coalesce_rejected(self):
        with pytest.raises(ValueError, match="pod-coalesce-variants"):
            self._driver("sparse", pod_coalesce_variants=-8)

    def test_dense_panel_width_buckets(self):
        from spark_examples_tpu.ops.sparse import dense_panel_width

        # Power-of-two bucket, min 8, capped at the block width; a
        # wider-than-block window (direct API use) keeps exact rows.
        assert dense_panel_width(512, 8192) == 512
        assert dense_panel_width(513, 8192) == 1024
        assert dense_panel_width(0, 8192) == 8
        assert dense_panel_width(3, 32) == 8
        assert dense_panel_width(8192, 8192) == 8192
        assert dense_panel_width(9000, 8192) == 9000

    def test_rare_variant_af_out_of_range_rejected(self):
        # af > 2/3 would silently saturate carrier probability past 1
        # (an all-carrier "rare" cohort); af <= 0 an all-zero one.
        for bad in (0.8, 0.0, -0.1):
            with pytest.raises(ValueError, match="rare_variant_af"):
                synthetic_cohort(4, 4, rare_variant_af=bad)


class TestStreamFootprintBound:
    """Satellite: the 16·N² host refusal (round-4 verdict) is gone;
    the bound is the streaming-sparse per-device G footprint."""

    def _driver(self, mesh_spec=None):
        conf = PcaConfig(
            variant_set_ids=[DEFAULT_VARIANT_SET_ID], block_variants=32
        )
        mesh = make_mesh(mesh_spec) if mesh_spec else None
        return VariantsPcaDriver(
            conf, synthetic_cohort(12, 90), mesh=mesh
        )

    def test_old_16n2_bound_is_gone(self):
        """A budget the historical 16·N² peak refused (anything under
        16·N²) now admits the run — the sparse engine never builds the
        int64 host G + f32 copy + jax buffer stack."""
        driver = self._driver()
        calls = list(driver.get_calls(driver.get_data()))
        out = driver.get_similarity_matrix_stream(
            iter(calls), max_device_bytes=16 * 12 * 12 - 1
        )
        assert out.shape == (12, 12)

    def test_refuses_past_per_host_footprint_with_new_message(self):
        driver = self._driver()
        calls = list(driver.get_calls(driver.get_data()))
        with pytest.raises(
            ValueError, match="per-device f32 Gramian tile"
        ) as exc:
            driver.get_similarity_matrix_stream(
                iter(calls), max_device_bytes=4 * 12 * 12 - 1
            )
        assert "max_device_bytes" in str(exc.value)
        # AT the f32-G footprint it runs.
        out = driver.get_similarity_matrix_stream(
            iter(calls), max_device_bytes=4 * 12 * 12
        )
        assert out.shape == (12, 12)

    def test_stream_bit_identical_through_sparse_engine(self):
        driver = self._driver()
        calls = list(driver.get_calls(driver.get_data()))
        dense = np.asarray(driver.get_similarity_matrix(iter(calls)))
        stream = np.asarray(
            driver.get_similarity_matrix_stream(iter(calls))
        )
        np.testing.assert_array_equal(dense, stream)

    def test_mesh_footprint_accounts_tiles(self):
        meshed = self._driver("data:2,model:2")
        meshless = self._driver()
        # The bound is one device's tile: each of the 2x2 grid's
        # devices holds a 6 x 6 quarter of G; meshless, one device holds
        # all of it.
        assert meshed._sparse_device_g_bytes() == 4 * 6 * 6
        assert meshless._sparse_device_g_bytes() == 4 * 12 * 12


class TestPanelWidthConvention:
    """Satellite: the k+1-values calling convention lives in ONE helper
    so the sharded finish can't silently drop the gap check."""

    def test_floor_and_cap(self):
        assert randomized_panel_width(100, 2, 8) == 10
        assert randomized_panel_width(100, 2, 0) == 3  # k+1 floor
        assert randomized_panel_width(100, 2, -5) == 3
        assert randomized_panel_width(3, 2, 8) == 3  # n cap
        with pytest.raises(ValueError, match="k >= 1"):
            randomized_panel_width(10, 0, 8)

    def test_zero_oversample_still_checks_the_gap(self):
        """Before centralizing, oversample=0 silently disabled the
        spectral-gap degeneracy warning (no k+1-th Ritz value); now the
        panel floor guarantees it."""
        rng = np.random.default_rng(1)
        q, _ = np.linalg.qr(rng.random((48, 48)))
        w = np.concatenate([[10.0, 5.0, 4.999], np.linspace(1, 0.1, 45)])
        c = jnp.asarray((q * w) @ q.T, jnp.float32)
        with pytest.warns(Warning, match="near-degenerate"):
            vecs, vals = topk_eig_randomized(c, 2, oversample=0, iters=40)
        assert vecs.shape == (48, 2) and vals.shape == (2,)


class TestSparseCliTelemetry:
    def test_cli_sparse_run_emits_schema_valid_artifacts(self, tmp_path):
        from spark_examples_tpu.cli.main import main

        paths = {
            "trace": str(tmp_path / "run.trace.json"),
            "metrics": str(tmp_path / "run.metrics.prom"),
            "manifest": str(tmp_path / "run.manifest.json"),
        }
        old = os.environ.get("SPARK_EXAMPLES_TPU_COMPILE_CACHE")
        os.environ["SPARK_EXAMPLES_TPU_COMPILE_CACHE"] = "0"
        try:
            rc = main(
                [
                    "pca",
                    "--fixture-samples",
                    "16",
                    "--fixture-variants",
                    "96",
                    "--fixture-rare-af",
                    "0.05",
                    "--pca-mode",
                    "sparse",
                    "--mesh-shape",
                    "data:2,model:2",
                    "--trace-out",
                    paths["trace"],
                    "--metrics-out",
                    paths["metrics"],
                    "--manifest-out",
                    paths["manifest"],
                ]
            )
        finally:
            if old is None:
                os.environ.pop("SPARK_EXAMPLES_TPU_COMPILE_CACHE", None)
            else:
                os.environ["SPARK_EXAMPLES_TPU_COMPILE_CACHE"] = old
        assert rc == 0
        assert validate.validate_trace(paths["trace"]) == []
        assert validate.validate_metrics(paths["metrics"]) == []
        assert validate.validate_manifest(paths["manifest"]) == []
        trace = json.load(open(paths["trace"]))
        names = {ev.get("name") for ev in trace["traceEvents"]}
        assert "gramian.sparse.accumulate" in names
        assert "gramian.sparse.window" in names
        prom = open(paths["metrics"]).read()
        assert 'sparse_gramian_windows_total{route="' in prom
        assert "sparse_gramian_nnz_total" in prom


class TestSchemaDrift:
    """Satellite: both rejection directions for the pod-sparse obs
    surface — an unknown ``gramian.sparse.*`` span fails the trace
    gate, and a ``sparse_pod_sync_total`` sample without its outcome
    label fails the metrics gate (the closed sets GL003 cross-checks
    statically)."""

    def test_allgather_span_is_schema_known(self, tmp_path):
        trace = tmp_path / "t.json"
        trace.write_text(
            json.dumps(
                {
                    "traceEvents": [
                        {
                            "ph": "X",
                            "name": "gramian.sparse.allgather",
                            "pid": 1,
                            "ts": 0,
                            "dur": 1,
                        }
                    ]
                }
            )
        )
        assert validate.validate_trace(str(trace)) == []

    def test_unknown_sparse_span_rejected(self, tmp_path):
        trace = tmp_path / "t.json"
        trace.write_text(
            json.dumps(
                {
                    "traceEvents": [
                        {
                            "ph": "X",
                            "name": "gramian.sparse.carrier_sync",
                            "pid": 1,
                            "ts": 0,
                            "dur": 1,
                        }
                    ]
                }
            )
        )
        errs = validate.validate_trace(str(trace))
        assert errs and "gramian.sparse.carrier_sync" in errs[0]

    def test_pod_sync_counter_requires_outcome_label(self, tmp_path):
        good = tmp_path / "good.prom"
        good.write_text('sparse_pod_sync_total{outcome="synced"} 3\n')
        assert validate.validate_metrics(str(good)) == []
        bad = tmp_path / "bad.prom"
        bad.write_text("sparse_pod_sync_total 3\n")
        errs = validate.validate_metrics(str(bad))
        assert errs and "outcome" in errs[0]

    def test_slot_span_is_schema_known(self, tmp_path):
        trace = tmp_path / "t.json"
        trace.write_text(
            json.dumps(
                {
                    "traceEvents": [
                        {
                            "ph": "X",
                            "name": "gramian.sparse.slot",
                            "pid": 1,
                            "ts": 0,
                            "dur": 1,
                        }
                    ]
                }
            )
        )
        assert validate.validate_trace(str(trace)) == []

    def test_unknown_pipeline_span_rejected(self, tmp_path):
        trace = tmp_path / "t.json"
        trace.write_text(
            json.dumps(
                {
                    "traceEvents": [
                        {
                            "ph": "X",
                            "name": "gramian.sparse.pipeline_slot",
                            "pid": 1,
                            "ts": 0,
                            "dur": 1,
                        }
                    ]
                }
            )
        )
        errs = validate.validate_trace(str(trace))
        assert errs and "gramian.sparse.pipeline_slot" in errs[0]

    def test_coalesce_counter_requires_mode_label(self, tmp_path):
        good = tmp_path / "good.prom"
        good.write_text(
            'sparse_pod_coalesced_windows_total{mode="gang"} 12\n'
        )
        assert validate.validate_metrics(str(good)) == []
        bad = tmp_path / "bad.prom"
        bad.write_text("sparse_pod_coalesced_windows_total 12\n")
        errs = validate.validate_metrics(str(bad))
        assert errs and "mode" in errs[0]


# ---------------------------------------------------------------------------
# Process-spanning (pod) sparse protocol: subprocess-spawned
# jax.distributed CPU harness (2 and 4 processes). Same worker pattern
# as tests/test_multihost.py; every scenario runs under a hard timeout
# so a stranded-peer deadlock fails the test instead of hanging it.
# ---------------------------------------------------------------------------

import socket  # noqa: E402
import subprocess  # noqa: E402
import textwrap  # noqa: E402

pod_skip = pytest.mark.skipif(
    os.environ.get("SPARK_EXAMPLES_TPU_SKIP_MULTIHOST") == "1",
    reason="multihost tests disabled",
)


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _run_pod_workers(script_path, argv, n=2, timeout=300):
    """Spawn n coordinator-connected workers; assert every one exits 0
    within the hard timeout (a hung collective must FAIL, never hang
    the suite — dead peers are killed in the finally)."""
    port = _free_port()
    env = {
        **os.environ,
        "PYTHONPATH": os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))
        ),
        "JAX_COORDINATOR_ADDRESS": f"127.0.0.1:{port}",
        "JAX_NUM_PROCESSES": str(n),
        "XLA_FLAGS": "--xla_force_host_platform_device_count=2",
        # The collective-congruence runtime backstop runs for the whole
        # multiprocess suite: every protocol step cross-checks its
        # derived (op, geometry) digest across peers, so a divergence
        # bug fails loudly here instead of deadlocking a real pod.
        "SPARK_EXAMPLES_TPU_COLLECTIVE_CHECK": "1",
    }
    procs = [
        subprocess.Popen(
            [sys.executable, str(script_path)] + [str(a) for a in argv],
            env={**env, "JAX_PROCESS_ID": str(i)},
            stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT,
        )
        for i in range(n)
    ]
    try:
        logs = [p.communicate(timeout=timeout)[0].decode() for p in procs]
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    for p, log in zip(procs, logs):
        assert p.returncode == 0, log[-3000:]
    return logs


_POD_SPARSE_WORKER = textwrap.dedent(
    """
    import json, os, sys
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    jax.config.update("jax_platforms", "cpu")
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from spark_examples_tpu.parallel.distributed import initialize_from_env
    assert initialize_from_env()
    from spark_examples_tpu.arrays.blocks import csr_windows
    from spark_examples_tpu.parallel.sharded import (
        sparse_sharded_gramian_blockwise,
    )

    pid, world = jax.process_index(), jax.process_count()
    mesh = Mesh(np.array(jax.devices()).reshape(world, 2), ("data", "model"))
    rep = NamedSharding(mesh, P(None, None))
    replicate = jax.jit(lambda a: a, out_shardings=rep)

    def cohort(n, v, density, seed):
        rng = np.random.default_rng(seed)
        x = (rng.random((n, v)) < density).astype(np.int8)
        cols, rows = np.nonzero(x.T)
        lens = np.bincount(cols, minlength=v)
        offsets = np.zeros(v + 1, np.int64)
        np.cumsum(lens, out=offsets[1:])
        return x, (rows.astype(np.int64), offsets)

    out = {}
    n = 37
    x, pair = cohort(n, 300, 0.06, seed=1)
    windows = list(csr_windows(iter([pair]), 32))
    mine = windows[pid::world]  # uneven per-process streams (tail)

    # 1. Pod-sparse G over round-robin window slices, manifest order.
    g = sparse_sharded_gramian_blockwise(
        iter(mine), n, mesh, block_variants=32
    )
    assert not g.is_fully_addressable  # really cross-process sharded
    out["g"] = np.asarray(replicate(g)).tolist()
    out["tile_shapes"] = sorted(
        str(s.data.shape) for s in g.addressable_shards
    )

    # 2. Shuffled window order (each process shuffles its own slice) —
    # integer-exact accumulation is order-invariant.
    rng = np.random.default_rng(3 + pid)
    shuffled = [mine[i] for i in rng.permutation(len(mine))]
    g2 = sparse_sharded_gramian_blockwise(
        iter(shuffled), n, mesh, block_variants=32
    )
    out["g_shuffled"] = np.asarray(replicate(g2)).tolist()

    # 3. Density edges: all-zero window, single-nnz row, and a mixed
    # dense+scatter stream where SAME-STEP windows agree on the route
    # (steps 0-1 scatter everywhere, step 2 dense everywhere: density
    # 12/19 >= 0.5 on every process) — the pod route is a per-step
    # global decision, and the per-route window counter pins that the
    # dense pod payload branch REALLY ran (not just scatter twice).
    from spark_examples_tpu import obs
    cnt = obs.get_registry().counter(
        "sparse_gramian_windows_total",
        "CSR windows accumulated by the sparse-aware Gramian engine",
    )
    before = {r: cnt.labels(route=r).value for r in ("scatter", "dense")}
    edge = [
        (np.zeros(0, np.int64), np.zeros(8, np.int64)),       # all-zero
        (np.array([4 + pid], np.int64), np.array([1], np.int64)),
        (
            np.arange(12, dtype=np.int64),                     # dense step
            np.array([12], np.int64),
        ),
    ]
    g3 = sparse_sharded_gramian_blockwise(
        iter(edge), 19, mesh, density_threshold=0.5, block_variants=8
    )
    out["g_edges"] = np.asarray(
        jax.jit(lambda a: a, out_shardings=rep)(g3)
    ).tolist()
    out["edge_routes"] = {
        r: cnt.labels(route=r).value - before[r]
        for r in ("scatter", "dense")
    }

    # 4. Forced sparse on a HOST-LOCAL mesh in this multi-controller
    # run: each process tiles only ITS slice over its OWN devices with
    # zero collectives, so the result is a per-host partial — the
    # driver-side allreduce_gramian merge (pca._windows_to_gramian's
    # non-spanning multi-process branch) must reproduce the global G.
    from spark_examples_tpu.parallel.distributed import allreduce_gramian
    local_mesh = Mesh(
        np.array(jax.local_devices()).reshape(1, -1), ("data", "model")
    )
    g4 = sparse_sharded_gramian_blockwise(
        iter(mine), n, local_mesh, block_variants=32
    )
    assert g4.is_fully_addressable
    out["g_hostlocal_merged"] = np.asarray(allreduce_gramian(g4)).tolist()

    # 5. Pipeline-depth ablation: depth 0 (inline lockstep) and a deep
    # pipeline produce bit-identical G — the pipeline changes WHEN the
    # exchange runs, never what accumulates.
    g5 = sparse_sharded_gramian_blockwise(
        iter(mine), n, mesh, block_variants=32, pipeline_depth=0
    )
    out["g_depth0"] = np.asarray(replicate(g5)).tolist()
    g6 = sparse_sharded_gramian_blockwise(
        iter(mine), n, mesh, block_variants=32, pipeline_depth=4
    )
    out["g_depth4"] = np.asarray(replicate(g6)).tolist()

    # 6. Coalesced-gang bit-identity: many TINY windows (well under the
    # coalesce target) merge into multi-window gangs; shuffled local
    # orders and different coalesce settings all land on the same G,
    # and the gang/solo counter records the split.
    gang_counter = obs.get_registry().counter(
        "sparse_pod_coalesced_windows_total",
        "Local CSR windows entering pod-sparse protocol steps, by "
        "gang/solo coalescing outcome",
    )
    tiny = list(csr_windows(iter([pair]), 4))  # 4-variant windows
    mine_tiny = tiny[pid::world]
    before_gang = {
        m: gang_counter.labels(mode=m).value for m in ("gang", "solo")
    }
    for coalesce, key in ((0, "g_solo"), (64, "g_gang")):
        rng = np.random.default_rng(11 + pid + coalesce)
        shuffled_tiny = [
            mine_tiny[i] for i in rng.permutation(len(mine_tiny))
        ]
        gg = sparse_sharded_gramian_blockwise(
            iter(shuffled_tiny), n, mesh, block_variants=4,
            coalesce_variants=coalesce,
            density_threshold=1.01,  # all-scatter: gangs can form
        )
        out[key] = np.asarray(replicate(gg)).tolist()
    after_gang = {
        m: gang_counter.labels(mode=m).value for m in ("gang", "solo")
    }
    out["gang_delta"] = {
        m: after_gang[m] - before_gang[m] for m in ("gang", "solo")
    }
    out["tiny_windows"] = len(mine_tiny)

    # 7. Overlap proof on the emitted trace: with the pipelined stream,
    # some step w+1 exchange span must BEGIN before step w's scatter
    # span ENDS (the serialization MULTICHIP_r06 paid is gone).
    from spark_examples_tpu.obs import telemetry_session
    trace_path = sys.argv[1] + f".trace.{pid}.json"
    with telemetry_session(trace_out=trace_path):
        sparse_sharded_gramian_blockwise(
            iter(mine), n, mesh, block_variants=32
        )
    import spark_examples_tpu as _pkg
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(_pkg.__file__))),
        "scripts",
    ))
    import validate_trace as _vt
    evs = json.load(open(trace_path))["traceEvents"]
    out["overlap_proven"] = _vt.sparse_overlap_proven(evs)
    out["slot_spans"] = sum(
        1
        for e in evs
        if e.get("ph") == "X" and e["name"] == "gramian.sparse.slot"
    )

    # 8. The TPU's scatter rule on the pod stream (backend patched):
    # steps whose gathered matrix fits int8 take the MXU tile update; a
    # step whose carrier repeats 130 times at one site takes the scan.
    paths = obs.get_registry().counter("sparse_scatter_path_windows_total")
    before_paths = {p: paths.labels(path=p).value for p in ("scan", "mxu-tile")}
    cpu_backend = jax.default_backend
    jax.default_backend = lambda: "tpu"
    try:
        g8 = sparse_sharded_gramian_blockwise(
            iter(mine), n, mesh, block_variants=32,
            density_threshold=1.01,  # every step on the scatter route
        )
        out["g_mxu_tile"] = np.asarray(replicate(g8)).tolist()
        repeat = (np.full(130, pid, np.int64), np.array([130], np.int64))
        g9 = sparse_sharded_gramian_blockwise(
            iter([repeat]), 19, mesh, density_threshold=1e9,
            block_variants=8,
        )
        out["g_past_int8"] = np.asarray(replicate(g9)).tolist()
    finally:
        jax.default_backend = cpu_backend
    out["pod_paths"] = {
        p: paths.labels(path=p).value - before_paths[p]
        for p in ("scan", "mxu-tile")
    }

    if pid == 0:
        with open(sys.argv[1], "w") as f:
            json.dump(out, f)
    """
)


_POD_CHAOS_WORKER = textwrap.dedent(
    """
    import json, os, sys
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    jax.config.update("jax_platforms", "cpu")
    import numpy as np
    from jax.sharding import Mesh

    from spark_examples_tpu.parallel.distributed import initialize_from_env
    assert initialize_from_env()
    from spark_examples_tpu.parallel.sharded import (
        sparse_sharded_gramian_blockwise,
    )
    from spark_examples_tpu import obs

    pid, world = jax.process_index(), jax.process_count()
    mesh = Mesh(np.array(jax.devices()).reshape(world, 2), ("data", "model"))
    results = {}
    DEPTH = 2
    # Failure positions 0..3 cover every in-flight slot of the depth-2
    # pipeline (position 0 = first slot, 3 = past the staged window).
    POSITIONS = [0, 1, 2, 3]

    def win(idx, lens):
        return np.asarray(idx, np.int64), np.asarray(lens, np.int64)

    def good(i):
        # Distinct tiny scatter windows (threshold 1.01 -> scatter).
        return win([i % 9], [1])

    # A. Producer exception on ONE process at slot position p must
    # raise on EVERY process together, at the same step — never a
    # stranded peer, whatever the pipeline had in flight.
    def failing(pid, p):
        for i in range(p):
            yield good(i)
        if pid == 0:
            raise IOError("injected mid-stream ingest failure")
        yield good(p)

    results["chaos"] = []
    for p in POSITIONS:
        try:
            sparse_sharded_gramian_blockwise(
                failing(pid, p), 9, mesh, density_threshold=1.01,
                pipeline_depth=DEPTH, coalesce_variants=0,
            )
            results["chaos"].append(False)
        except RuntimeError as e:
            ok = "carrier stream failed on process(es) [0]" in str(e)
            if pid == 0:
                ok = ok and isinstance(e.__cause__, IOError)
            else:
                ok = ok and e.__cause__ is None
            results["chaos"].append(ok)

    # B. Same-step route divergence at slot position p (one process's
    # window densifies, the peers' scatter) is a per-window GLOBAL
    # decision: ValueError on every process together.
    def divergent(pid, p):
        for i in range(p):
            yield good(i)
        if pid == 0:
            yield win(np.arange(6), [6])  # density 6/9 -> dense
        else:
            yield win([0], [1])           # density 1/9 -> scatter
    results["divergence"] = []
    for p in POSITIONS:
        try:
            sparse_sharded_gramian_blockwise(
                divergent(pid, p), 9, mesh, density_threshold=0.5,
                pipeline_depth=DEPTH, coalesce_variants=0,
            )
            results["divergence"].append(False)
        except ValueError as e:
            results["divergence"].append(
                "density route" in str(e)
                and "--sparse-density-threshold" in str(e)
            )

    # C. Payload construction failure AFTER the header sync (the
    # densify-OOM shape) at slot position p: _densify_window raises on
    # process 0 only — the payload-confirm exchange must turn it into
    # an all-process raise instead of stranding peers in the payload
    # phase.
    from spark_examples_tpu.arrays import blocks as _blocks

    real_densify = _blocks._densify_window

    def _oom(*a, **k):
        raise MemoryError("injected densify failure")

    def dense_tail(p):
        for i in range(p):
            yield win([i % 19], [1])      # 1/19 < 0.5 -> scatter
        yield win(np.arange(12), [12])    # 12/19 >= 0.5 -> dense
    results["payload"] = []
    for p in POSITIONS:
        if pid == 0:
            _blocks._densify_window = _oom
        try:
            sparse_sharded_gramian_blockwise(
                dense_tail(p), 19, mesh, density_threshold=0.5,
                pipeline_depth=DEPTH, coalesce_variants=0,
            )
            results["payload"].append(False)
        except RuntimeError as e:
            ok = (
                "carrier payload construction failed on process(es) [0]"
                in str(e)
            )
            if pid == 0:
                ok = ok and isinstance(e.__cause__, MemoryError)
            else:
                ok = ok and e.__cause__ is None
            results["payload"].append(ok)
        finally:
            _blocks._densify_window = real_densify

    # D. The sync counter recorded every outcome on every process:
    # one producer-error per A and per C scenario, one
    # route-divergence per B scenario, and exactly the good slots
    # BEFORE each failure as synced (sum over positions, x3 kinds).
    counter = obs.get_registry().counter(
        "sparse_pod_sync_total",
        "Pod-sparse per-window sync steps (header + carrier allgather) "
        "by outcome",
    )
    results["outcomes"] = {
        o: counter.labels(outcome=o).value
        for o in ("synced", "producer-error", "route-divergence")
    }
    results["expected"] = {
        "synced": 3 * sum(POSITIONS),
        "producer-error": 2 * len(POSITIONS),
        "route-divergence": len(POSITIONS),
    }
    with open(sys.argv[1] + f".{pid}", "w") as f:
        json.dump(results, f)
    """
)


_POD_COLLECTIVE_CHECK_WORKER = textwrap.dedent(
    """
    import json, os, re, sys
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    jax.config.update("jax_platforms", "cpu")
    import numpy as np
    from jax.sharding import Mesh

    from spark_examples_tpu.parallel.distributed import initialize_from_env
    assert initialize_from_env()
    from spark_examples_tpu.parallel.sharded import (
        sparse_sharded_gramian_blockwise,
    )
    from spark_examples_tpu.utils import collectivecheck
    from spark_examples_tpu import obs

    pid, world = jax.process_index(), jax.process_count()
    mesh = Mesh(np.array(jax.devices()).reshape(world, 2), ("data", "model"))
    results = {}
    assert collectivecheck.collective_check_enabled()  # harness env

    counter = obs.get_registry().counter(
        "collective_check_steps_total",
        "Pod protocol steps cross-checked by the collective-congruence "
        "runtime backstop, by outcome",
    )

    def win(i):
        return np.asarray([i % 9], np.int64), np.asarray([1], np.int64)

    # A. Clean run with the backstop ON: bit-identical G, every live
    # step cross-checked and counted as agree.
    before = counter.labels(outcome="agree").value
    g = sparse_sharded_gramian_blockwise(
        iter([win(i) for i in range(4)]), 9, mesh,
        density_threshold=1.01, pipeline_depth=2, coalesce_variants=0,
    )
    from jax.sharding import NamedSharding, PartitionSpec as P
    rep = NamedSharding(mesh, P(None, None))
    results["g"] = np.asarray(
        jax.jit(lambda a: a, out_shardings=rep)(g)
    ).tolist()
    results["agree_clean"] = counter.labels(outcome="agree").value - before

    # B. Chaos: a one-sided extra/omitted collective, injected through
    # the fault seam on the podstream step hash — process 0's digest
    # diverges at FAULT_STEP. The backstop must raise on EVERY process
    # at the SAME step (never a stranded peer).
    FAULT_STEP = 1
    real_digest = collectivecheck.step_digest

    def faulty(stream, step, ops):
        d = real_digest(stream, step, ops)
        if pid == 0 and step == FAULT_STEP:
            # Simulate an extra collective in the derived sequence.
            d = real_digest(stream, step, list(ops) + [("psum", (9,))])
        return d

    collectivecheck.step_digest = faulty
    try:
        sparse_sharded_gramian_blockwise(
            iter([win(i) for i in range(6)]), 9, mesh,
            density_threshold=1.01, pipeline_depth=2,
            coalesce_variants=0,
        )
        results["raised"] = False
    except RuntimeError as e:
        msg = str(e)
        m = re.search(r"protocol step (\\d+)", msg)
        results["raised"] = (
            "collective-congruence check failed" in msg
            and "digests diverged" in msg
        )
        results["step"] = int(m.group(1)) if m else -1
    finally:
        collectivecheck.step_digest = real_digest
    results["divergence"] = counter.labels(outcome="divergence").value

    with open(sys.argv[1] + f".{pid}", "w") as f:
        json.dump(results, f)
    """
)


@pod_skip
class TestPodSparseProtocol:
    """The per-step carrier-allgather protocol on a REAL ≥2-process
    ``jax.distributed`` CPU mesh: G bit-identical across
    {single-controller sparse, pod-sparse, dense reference} × shuffled
    window orders × density edges, and the failure-sync chaos cases."""

    @pytest.mark.parametrize("nprocs", [2, 4])
    def test_pod_sparse_bit_identical_to_dense(self, tmp_path, nprocs):
        if nprocs > (os.cpu_count() or 1) * 4:
            pytest.skip("not enough cores to host the pod-sim")
        script = tmp_path / "worker.py"
        script.write_text(_POD_SPARSE_WORKER)
        out_file = tmp_path / "result.json"
        _run_pod_workers(script, [out_file], n=nprocs)
        result = json.loads(out_file.read_text())

        # Dense reference + single-controller sparse over the SAME
        # cohort the pod split round-robin (cohort_csr(seed=1) is the
        # worker's generator, bit for bit).
        x, pair = cohort_csr(37, 300, density=0.06, seed=1)
        want = np.asarray(gramian(x))
        single = np.asarray(
            sparse_gramian_blockwise(
                csr_windows(iter([pair]), 32), 37, block_variants=32
            )
        )
        got = np.asarray(result["g"])
        np.testing.assert_array_equal(got, want)
        np.testing.assert_array_equal(got, single)
        np.testing.assert_array_equal(
            np.asarray(result["g_shuffled"]), want
        )

        # Density edges: the expectation is the union of every
        # process's edge windows (all-zero + per-process single-nnz +
        # one identical dense window each); the route counter pins
        # that the stream REALLY split 2 scatter + 1 dense steps.
        want_e = np.zeros((19, 19), np.float32)
        for p in range(nprocs):
            want_e[4 + p, 4 + p] += 1
        r = np.arange(12)
        want_e[np.ix_(r, r)] += nprocs
        np.testing.assert_array_equal(
            np.asarray(result["g_edges"]), want_e
        )
        assert result["edge_routes"] == {"scatter": 2, "dense": 1}

        # The host-local-mesh partial + DCN merge (the forced-sparse
        # multi-controller driver route) reproduces the global G.
        np.testing.assert_array_equal(
            np.asarray(result["g_hostlocal_merged"]), want
        )

        # Pipeline-depth ablation: inline lockstep (0) and a deep
        # pipeline (4) are bit-identical to the default-depth run.
        np.testing.assert_array_equal(np.asarray(result["g_depth0"]), want)
        np.testing.assert_array_equal(np.asarray(result["g_depth4"]), want)

        # Coalesced gangs: tiny windows, shuffled per-process orders,
        # with coalescing off and on — bit-identical G both ways, and
        # the gang/solo counter recorded every window on the right
        # side (no 1-window gangs at these sizes: 4-variant windows
        # against a 64-variant target).
        np.testing.assert_array_equal(np.asarray(result["g_solo"]), want)
        np.testing.assert_array_equal(np.asarray(result["g_gang"]), want)
        assert result["gang_delta"] == {
            "gang": result["tiny_windows"],
            "solo": result["tiny_windows"],
        }

        # The pipelined stream PROVABLY overlapped: a step w+1 exchange
        # span began before step w's scatter span ended, and slot spans
        # made it onto the timeline.
        assert result["overlap_proven"] is True
        assert result["slot_spans"] >= 2

        # The TPU's rule on the pod stream: the MXU tile update (mirrored
        # on the square 2-process grid) gives the same G; the step past
        # int8 took the scan, exactly.
        np.testing.assert_array_equal(np.asarray(result["g_mxu_tile"]), want)
        want_r = np.zeros((19, 19), np.float32)
        for p in range(nprocs):
            want_r[p, p] = 130.0**2
        np.testing.assert_array_equal(
            np.asarray(result["g_past_int8"]), want_r
        )
        assert result["pod_paths"]["scan"] == 1
        assert result["pod_paths"]["mxu-tile"] >= 1

    @pytest.mark.parametrize("nprocs", [2, 4])
    def test_pod_failure_sync_chaos(self, tmp_path, nprocs):
        """One-sided producer failures (mid-stream AND post-header
        payload construction) and same-step route divergence, injected
        at EVERY in-flight slot position of the depth-2 pipeline, raise
        on EVERY process together — each run completes (no hang) under
        the harness's hard timeout, and the per-outcome sync counters
        account for exactly the slots that completed before each
        failure."""
        if nprocs > (os.cpu_count() or 1) * 4:
            pytest.skip("not enough cores to host the pod-sim")
        script = tmp_path / "worker.py"
        script.write_text(_POD_CHAOS_WORKER)
        out_file = tmp_path / "result.json"
        _run_pod_workers(script, [out_file], n=nprocs, timeout=240)
        for pid in range(nprocs):
            r = json.loads((tmp_path / f"result.json.{pid}").read_text())
            assert all(r["chaos"]), r
            assert all(r["divergence"]), r
            assert all(r["payload"]), r
            assert r["outcomes"] == r["expected"], r

    @pytest.mark.parametrize("nprocs", [2, 4])
    def test_collective_check_divergence_chaos(self, tmp_path, nprocs):
        """The SPARK_EXAMPLES_TPU_COLLECTIVE_CHECK=1 backstop: a
        one-sided extra collective (injected through the fault seam on
        the podstream step hash) raises on EVERY process at the SAME
        step, while a clean run stays bit-identical with every live
        step counted as agree."""
        if nprocs > (os.cpu_count() or 1) * 4:
            pytest.skip("not enough cores to host the pod-sim")
        script = tmp_path / "worker.py"
        script.write_text(_POD_COLLECTIVE_CHECK_WORKER)
        out_file = tmp_path / "result.json"
        _run_pod_workers(script, [out_file], n=nprocs, timeout=240)

        # The clean phase's G equals the dense reference over the
        # union of every process's windows (each process scatters
        # win(0..3): +1 on the diagonal at 0..3 per process).
        want = np.zeros((9, 9), np.float32)
        for i in range(4):
            want[i % 9, i % 9] += nprocs
        steps = set()
        for pid in range(nprocs):
            r = json.loads((tmp_path / f"result.json.{pid}").read_text())
            np.testing.assert_array_equal(np.asarray(r["g"]), want)
            assert r["agree_clean"] == 4, r
            assert r["raised"] is True, r
            assert r["divergence"] >= 1, r
            steps.add(r["step"])
        # ... and the raise landed at the SAME step everywhere.
        assert steps == {1}, steps


@pytest.mark.slow
def test_biobank_scale_65k_end_to_end_on_mesh():
    """ROADMAP item 2 acceptance: a synthetic N=65536 rare-variant
    cohort end to end on a ≥4-device host mesh through
    ``cli pca --pca-mode sparse`` — G tiled (N/2, N/2) per device (no
    N×N on any single device), finish through the sharded randomized
    eig. CPU backend; takes minutes (17 GB of f32 G tiles)."""
    from spark_examples_tpu.cli.main import main

    import tempfile

    n = 65536
    with tempfile.TemporaryDirectory() as tmp:
        out = os.path.join(tmp, "out")
        rc = main(
            [
                "pca",
                "--fixture-samples",
                str(n),
                "--fixture-variants",
                "64",
                "--fixture-rare-af",
                "0.003",
                "--fixture-sparse-calls",
                "--pca-mode",
                "sparse",
                "--mesh-shape",
                "data:2,model:2",
                "--eig-tol",
                "1e-3",
                "--output-path",
                out,
            ]
        )
        assert rc == 0
        lines = open(out + "-pca.tsv").read().splitlines()
        assert len(lines) == n
