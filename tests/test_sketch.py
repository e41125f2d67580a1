"""Gramian-free sketch PCA suite (``--pca-mode sketch``).

Pins the fourth PCA engine end to end: tolerance-pinned spectrum
goldens vs the exact eigendecomposition at small N across mesh shapes
(1×1, 2×1, 2×2), shuffled window orders, and density edge cases;
seeded-Ω reproducibility (bit-identical per seed, tolerance-equal
across seeds); the O(N·(k+p)) footprint bound that replaces the N²
tile; the PCA_MODES registry/flag/error-message three-way sync; the
serving JobSpec surface (sketch keys join every sketch knob, exact
keys stay historical); the telemetry closed sets in BOTH rejection
directions; and the 2-process pod-sim protocol leg.
"""

import argparse
import json
import os
import sys
import textwrap

import numpy as np
import pytest

from spark_examples_tpu.arrays.blocks import csr_windows
from spark_examples_tpu.genomics.fixtures import (
    DEFAULT_VARIANT_SET_ID,
    synthetic_cohort,
)
from spark_examples_tpu.models.pca import (
    PCA_MODES as DRIVER_PCA_MODES,
    VariantsPcaDriver,
)
from spark_examples_tpu.ops.pcoa import (
    normalize_eigvec_signs,
    randomized_panel_width,
)
from spark_examples_tpu.ops.sketch import (
    SKETCH_FULLRANK_ATOL,
    SKETCH_FULLRANK_RTOL,
    SKETCH_TOPK_ATOL,
    SKETCH_TOPK_RTOL,
    gaussian_test_matrix,
    sketch_eig,
    sketch_host_bytes,
    sketch_panel_blockwise,
)
from spark_examples_tpu.parallel.mesh import make_mesh
from spark_examples_tpu.parallel.sharded import sharded_sketch_panel
from spark_examples_tpu.serving.jobs import (
    JobSpec,
    cohort_key,
    job_config,
    resolve_spec,
)
from spark_examples_tpu.utils.config import (
    PCA_MODES,
    PcaConfig,
    add_pca_flags,
)

sys.path.insert(
    0,
    os.path.join(os.path.dirname(os.path.dirname(__file__)), "scripts"),
)
import validate_trace as validate  # noqa: E402

import jax  # noqa: E402  (after conftest has pinned the platform)

MESH_SPECS = tuple(
    spec
    for spec, need in (
        ("data:1", 1),
        ("data:2", 2),
        ("data:2,model:2", 4),
    )
    if need <= jax.device_count()
)

K = 2
N, V = 36, 240


def structured_csr(n=N, v=V, seed=0, pops=3):
    """Dense X + CSR twin with ``pops`` well-separated populations:
    population-aligned common variants give the centered Gramian a
    clean top-(pops−1) spectrum (gap far from the 0.95 warning bar) —
    the regime the sketch tolerance contract is pinned in."""
    rng = np.random.default_rng(seed)
    labels = np.arange(n) % pops
    x = np.zeros((n, v), np.int8)
    for j in range(v):
        p_carry = np.where(labels == (j % pops), 0.85, 0.05)
        x[:, j] = rng.random(n) < p_carry
    cols, rows = np.nonzero(x.T)
    lens = np.bincount(cols, minlength=v)
    offsets = np.zeros(v + 1, np.int64)
    np.cumsum(lens, out=offsets[1:])
    return x, (rows.astype(np.int64), offsets)


def exact_eig(x, k=K):
    """The exact reference surface: sign-normalized unit eigenvectors
    and eigenvalues of the centered Gramian C = H·XXᵀ·H in f64."""
    xf = np.asarray(x, np.float64)
    g = xf @ xf.T
    n = g.shape[0]
    h = np.eye(n) - 1.0 / n
    c = h @ g @ h
    w, u = np.linalg.eigh(c)
    order = np.argsort(w)[::-1][:k]
    return normalize_eigvec_signs(u[:, order]), w[order]


def _panel(pair, n=N, k=K, mesh=None, power_iters=2, block=32, **kw):
    factory = lambda: csr_windows(iter([pair]), block)  # noqa: E731
    if mesh is not None:
        return sharded_sketch_panel(
            factory,
            n,
            k,
            mesh,
            power_iters=power_iters,
            block_variants=block,
            **kw,
        )
    return sketch_panel_blockwise(
        factory,
        n,
        k,
        power_iters=power_iters,
        block_variants=block,
        **kw,
    )


def assert_spectrum(coords, vals, ref_coords, ref_vals, rtol, atol):
    np.testing.assert_allclose(vals, ref_vals, rtol=rtol)
    assert np.abs(coords - ref_coords).max() <= atol


class TestSpectrumGoldens:
    """Tolerance-pinned goldens vs the exact path at small N — the
    module-docstring contract (full-rank and top-k regimes)."""

    def test_fixture_has_a_clean_gap(self):
        # The tolerance contract only holds past a clear spectral gap;
        # pin the fixture itself so a regression in it can't silently
        # relax every golden below.
        x, _ = structured_csr()
        _, vals = exact_eig(x, k=K + 1)
        assert vals[K] / vals[K - 1] < 0.5

    def test_meshless_topk_matches_exact(self):
        x, pair = structured_csr()
        coords, vals = sketch_eig(_panel(pair), K)
        ref_c, ref_v = exact_eig(x)
        assert_spectrum(
            coords, vals, ref_c, ref_v, SKETCH_TOPK_RTOL, SKETCH_TOPK_ATOL
        )

    @pytest.mark.parametrize("spec", MESH_SPECS)
    def test_mesh_topk_matches_exact(self, spec):
        x, pair = structured_csr()
        mesh = make_mesh(spec)
        coords, vals = sketch_eig(_panel(pair, mesh=mesh), K)
        ref_c, ref_v = exact_eig(x)
        assert_spectrum(
            coords, vals, ref_c, ref_v, SKETCH_TOPK_RTOL, SKETCH_TOPK_ATOL
        )

    def test_full_rank_matches_exact_tightly(self):
        # l = n: the Nyström reconstruction is exact up to roundoff.
        x, pair = structured_csr()
        panel = _panel(pair, power_iters=0, oversample=N)
        assert panel.l == N
        coords, vals = sketch_eig(panel, K)
        ref_c, ref_v = exact_eig(x)
        assert_spectrum(
            coords,
            vals,
            ref_c,
            ref_v,
            SKETCH_FULLRANK_RTOL,
            SKETCH_FULLRANK_ATOL,
        )

    def test_shuffled_window_order_within_tolerance(self):
        # The accumulation is a sum over windows — arrival order can
        # only move f32 roundoff, never the result.
        x, pair = structured_csr()
        windows = list(csr_windows(iter([pair]), 16))
        shuffled = [
            windows[i]
            for i in np.random.default_rng(7).permutation(len(windows))
        ]
        a, av = sketch_eig(
            sketch_panel_blockwise(
                lambda: iter(windows), N, K, power_iters=2
            ),
            K,
        )
        b, bv = sketch_eig(
            sketch_panel_blockwise(
                lambda: iter(shuffled), N, K, power_iters=2
            ),
            K,
        )
        assert np.abs(a - b).max() <= 1e-4
        np.testing.assert_allclose(av, bv, rtol=1e-5)
        ref_c, ref_v = exact_eig(x)
        assert_spectrum(
            a, av, ref_c, ref_v, SKETCH_TOPK_RTOL, SKETCH_TOPK_ATOL
        )

    def test_density_edge_windows_and_route_mix(self):
        # All-zero window, single-nnz window, and an all-carrier dense
        # window: both kernel routes feed one panel, and the route
        # counter records the split.
        from spark_examples_tpu import obs

        # n = 64 keeps the single-carrier window under BOTH scatter
        # gates (mean density and max per-variant fraction: 1/64 <
        # 0.02) while the all-carrier window routes dense.
        n = 64
        # Window C mixes a half-carrier variant (keeps the centered
        # Gramian rank 2 — all-carrier columns center away to zero)
        # with two all-carrier ones; its max carrier fraction routes
        # it dense either way.
        windows = [
            (np.empty(0, np.int64), np.zeros(3, np.int64)),
            (np.array([5], np.int64), np.array([1], np.int64)),
            (
                np.concatenate(
                    [
                        np.arange(32, dtype=np.int64),
                        np.arange(n, dtype=np.int64),
                        np.arange(n, dtype=np.int64),
                    ]
                ),
                np.array([32, n, n], np.int64),
            ),
        ]
        x = np.zeros((n, 7), np.int8)
        x[5, 3] = 1
        x[:32, 4] = 1
        x[:, 5] = 1
        x[:, 6] = 1
        counter = obs.get_registry().counter(
            "sketch_windows_total",
            "CSR windows applied to the randomized sketch panel",
        )
        before = {
            r: counter.labels(route=r).value for r in ("scatter", "dense")
        }
        panel = sketch_panel_blockwise(
            lambda: iter(windows), n, K, power_iters=0
        )
        after = {
            r: counter.labels(route=r).value for r in ("scatter", "dense")
        }
        assert after["scatter"] - before["scatter"] == 2
        assert after["dense"] - before["dense"] == 1
        coords, vals = sketch_eig(panel, K)
        ref_c, ref_v = exact_eig(x)
        # The centered signal is rank 2 and l = 2+8 = 10 covers it
        # completely: the top-k contract applies without power
        # iterations.
        np.testing.assert_allclose(vals, ref_v, rtol=SKETCH_TOPK_RTOL)
        assert np.abs(coords - ref_c).max() <= SKETCH_TOPK_ATOL
        np.testing.assert_array_equal(
            panel.row_sums, (x.astype(np.float64) @ x.sum(0)).ravel()
        )

    def test_all_zero_cohort_yields_zero_coords(self):
        windows = [(np.empty(0, np.int64), np.zeros(4, np.int64))]
        panel = sketch_panel_blockwise(
            lambda: iter(windows), 9, K, power_iters=0
        )
        coords, vals = sketch_eig(panel, K)
        np.testing.assert_array_equal(coords, np.zeros((9, K)))
        np.testing.assert_array_equal(vals, np.zeros(K))


class TestReproducibility:
    """Seeded-Ω contract: same seed → bit-identical; different seeds →
    different panels that agree within the tolerance bars."""

    def test_same_seed_bit_identical(self):
        _, pair = structured_csr()
        a, av = sketch_eig(_panel(pair, seed=3), K)
        b, bv = sketch_eig(_panel(pair, seed=3), K)
        np.testing.assert_array_equal(a, b)
        np.testing.assert_array_equal(av, bv)

    def test_omega_is_seed_deterministic(self):
        np.testing.assert_array_equal(
            gaussian_test_matrix(20, 5, 11), gaussian_test_matrix(20, 5, 11)
        )
        assert (
            np.abs(
                gaussian_test_matrix(20, 5, 11)
                - gaussian_test_matrix(20, 5, 12)
            ).max()
            > 0
        )

    @pytest.mark.skipif(
        jax.device_count() < 2, reason="needs >= 2 devices"
    )
    def test_mesh_matches_meshless_same_seed(self):
        _, pair = structured_csr()
        a, av = sketch_eig(_panel(pair, seed=1), K)
        b, bv = sketch_eig(
            _panel(pair, mesh=make_mesh("data:2"), seed=1), K
        )
        assert np.abs(a - b).max() <= 1e-4
        np.testing.assert_allclose(av, bv, rtol=1e-5)

    def test_different_seeds_differ_within_bars(self):
        x, pair = structured_csr()
        a, av = sketch_eig(_panel(pair, seed=0), K)
        b, bv = sketch_eig(_panel(pair, seed=1), K)
        assert np.abs(a - b).max() > 0  # reproducible, NOT identical
        ref_c, ref_v = exact_eig(x)
        for coords, vals in ((a, av), (b, bv)):
            assert_spectrum(
                coords,
                vals,
                ref_c,
                ref_v,
                SKETCH_TOPK_RTOL,
                SKETCH_TOPK_ATOL,
            )


class TestFootprintBound:
    """The whole point of the engine: O(N·(k+p)) host bytes, never N²."""

    def test_bound_is_linear_not_quadratic(self):
        n = 1 << 20
        l = randomized_panel_width(n, 10)
        assert sketch_host_bytes(n, l) < (4 * n * n) // 1000
        assert sketch_host_bytes(2 * n, l) == pytest.approx(
            2 * sketch_host_bytes(n, l), rel=1e-6
        )

    def test_panel_arrays_within_documented_bound(self):
        _, pair = structured_csr()
        panel = _panel(pair)
        assert panel.host_peak_bytes == sketch_host_bytes(N, panel.l)
        assert panel.y.nbytes + panel.omega.nbytes <= panel.host_peak_bytes
        assert panel.y.shape == (N, panel.l)

    @pytest.mark.skipif(
        jax.device_count() < 2, reason="needs >= 2 devices"
    )
    def test_mesh_panel_bound_covers_padded_rows(self):
        _, pair = structured_csr()
        panel = _panel(pair, mesh=make_mesh("data:2"))
        n_padded = panel.y.shape[0]
        assert n_padded >= N
        assert panel.host_peak_bytes == sketch_host_bytes(
            n_padded, panel.l
        )
        # Padding rows carry no signal (C's padded block is zero).
        np.testing.assert_array_equal(panel.y[N:], 0.0)


class TestPcaModesRegistry:
    """Satellite: the ONE mode registry — argparse choices, driver
    validation message, and serving validation can never drift."""

    def test_registry_contents(self):
        assert PCA_MODES == ("auto", "fused", "stream", "sparse", "sketch")

    def test_models_reexports_the_same_registry(self):
        assert DRIVER_PCA_MODES is PCA_MODES

    def test_driver_error_lists_every_registered_mode(self):
        conf = PcaConfig(
            variant_set_ids=[DEFAULT_VARIANT_SET_ID], pca_mode="bogus"
        )
        src = synthetic_cohort(6, 12)
        with pytest.raises(ValueError) as err:
            VariantsPcaDriver(conf, src)
        for mode in PCA_MODES:
            assert repr(mode) in str(err.value)
        assert "'bogus'" in str(err.value)

    def test_cli_choices_are_the_registry(self):
        p = argparse.ArgumentParser()
        add_pca_flags(p)
        actions = {a.option_strings[0]: a for a in p._actions if a.option_strings}
        assert tuple(actions["--pca-mode"].choices) == PCA_MODES
        assert actions["--sketch-oversample"].default == (
            PcaConfig.sketch_oversample
        )
        assert actions["--sketch-seed"].default == PcaConfig.sketch_seed
        assert actions["--sketch-power-iters"].default == (
            PcaConfig.sketch_power_iters
        )

    def test_serving_validates_against_the_registry(self):
        with pytest.raises(ValueError, match="unknown pca_mode"):
            JobSpec.from_record({"pca_mode": "bogus"})
        for mode in PCA_MODES:
            assert JobSpec.from_record({"pca_mode": mode}).pca_mode == mode


class TestDriverSketchMode:
    def _driver(self, mode="sketch", mesh_spec=None, n=N, v=V, **kw):
        conf = PcaConfig(
            variant_set_ids=[DEFAULT_VARIANT_SET_ID],
            block_variants=64,
            pca_mode=mode,
            **kw,
        )
        mesh = make_mesh(mesh_spec) if mesh_spec else None
        source = synthetic_cohort(n, v, population_structure=3, seed=3)
        return VariantsPcaDriver(conf, source, mesh=mesh)

    def test_sketch_mode_matches_stream_coordinates(self):
        sketch = self._driver("sketch", sketch_power_iters=2).run()
        stream = self._driver("stream").run()
        a = np.array([r[1:] for r in sketch])
        b = np.array([r[1:] for r in stream])
        assert np.abs(a - b).max() <= SKETCH_TOPK_ATOL
        assert [r[0] for r in sketch] == [r[0] for r in stream]

    @pytest.mark.skipif(
        jax.device_count() < 4, reason="needs >= 4 devices"
    )
    def test_sketch_on_mesh_matches_meshless(self):
        a = np.array(
            [
                r[1:]
                for r in self._driver(
                    "sketch", "data:2,model:2", sketch_power_iters=2
                ).run()
            ]
        )
        b = np.array(
            [
                r[1:]
                for r in self._driver(
                    "sketch", sketch_power_iters=2
                ).run()
            ]
        )
        assert np.abs(a - b).max() <= 1e-4

    def test_nonzero_rows_parity_print_survives_without_g(self, capsys):
        self._driver("sketch").run()
        out_sketch = capsys.readouterr().out
        self._driver("stream").run()
        out_stream = capsys.readouterr().out
        line = [
            ln
            for ln in out_sketch.splitlines()
            if ln.startswith("Non zero rows in matrix:")
        ]
        assert line and line[0] in out_stream.splitlines()

    def test_sketch_selection(self, monkeypatch):
        assert self._driver("sketch").sketch_selected()  # forced
        assert not self._driver("stream").sketch_selected()
        # Auto stays exact at small N...
        auto = self._driver("auto")
        assert not auto.sketch_selected()
        # ...and flips to sketch exactly when the exact footprint bound
        # would refuse (the same 4 GiB line).
        monkeypatch.setattr(
            auto, "_sparse_device_g_bytes", lambda: (4 << 30) + 1
        )
        assert auto.sketch_selected()

    def test_sketch_rejects_checkpointing(self):
        with pytest.raises(ValueError, match="sketch"):
            self._driver("sketch", checkpoint_dir="/tmp/nope")

    def test_sketch_rejects_precise(self):
        with pytest.raises(ValueError, match="precise"):
            self._driver("sketch", precise=True)

    def test_bad_oversample_rejected(self):
        with pytest.raises(ValueError, match="sketch-oversample"):
            self._driver("sketch", sketch_oversample=0)

    def test_negative_power_iters_rejected(self):
        with pytest.raises(ValueError, match="sketch-power-iters"):
            self._driver("sketch", sketch_power_iters=-1)


class TestServingSketchSurface:
    """Key discipline: every exact engine is bit-identical, so exact
    keys never carry pca_mode (historical caches/journals keep their
    keys); a sketch job is a different artifact, so ALL its knobs join
    the key."""

    def _base(self, **kw):
        kw.setdefault("variant_set_ids", [DEFAULT_VARIANT_SET_ID])
        return PcaConfig(**kw)

    def test_exact_modes_share_historical_keys(self):
        base = self._base()
        plain = JobSpec.from_record({})
        assert cohort_key(plain, base) == cohort_key(
            JobSpec.from_record({"pca_mode": "sparse"}), base
        )
        assert "pca_mode" not in resolve_spec(plain, base)
        assert "sketch_seed" not in resolve_spec(plain, base)

    def test_sketch_key_is_distinct_and_seeded(self):
        base = self._base()
        sketch = JobSpec.from_record({"pca_mode": "sketch"})
        assert cohort_key(sketch, base) != cohort_key(
            JobSpec.from_record({}), base
        )
        resolved = resolve_spec(sketch, base)
        assert resolved["pca_mode"] == "sketch"
        assert resolved["sketch_oversample"] == base.sketch_oversample
        assert resolved["sketch_seed"] == base.sketch_seed
        assert resolved["sketch_power_iters"] == base.sketch_power_iters
        reseeded = self._base(sketch_seed=1)
        assert cohort_key(sketch, reseeded) != cohort_key(sketch, base)

    def test_spec_round_trip_and_journal_shape(self):
        spec = JobSpec.from_record({"pca_mode": "sketch"})
        rec = spec.to_record()
        assert rec["pca_mode"] == "sketch"
        assert JobSpec.from_record(rec) == spec
        # Pre-sketch journal records replay byte-identically: no key
        # appears on specs that never set one.
        assert "pca_mode" not in JobSpec.from_record({}).to_record()

    def test_pairhmm_rejects_pca_mode(self):
        with pytest.raises(ValueError, match="do not apply"):
            JobSpec.from_record(
                {"kind": "pairhmm", "pca_mode": "sketch"}
            )

    def test_job_config_strips_checkpoint_for_sketch(self):
        base = self._base(pca_mode="auto")
        conf = job_config(
            JobSpec.from_record({"pca_mode": "sketch"}),
            base,
            checkpoint_dir="/tmp/ckpt",
        )
        assert conf.pca_mode == "sketch"
        assert conf.checkpoint_dir is None
        exact = job_config(
            JobSpec.from_record({}), base, checkpoint_dir="/tmp/ckpt"
        )
        assert exact.checkpoint_dir == "/tmp/ckpt"

    def test_sketch_job_serves_end_to_end_and_never_gangs(self):
        from spark_examples_tpu.serving import (
            AnalysisEngine,
            AnalysisJobTier,
        )

        src = synthetic_cohort(12, 60, population_structure=3, seed=9)
        base = self._base(
            references="17:41196311:41277499",
            block_variants=16,
            sketch_power_iters=2,
        )
        tier = AnalysisJobTier(
            AnalysisEngine(src), base, workers=0, gang_max_samples=256
        )
        try:
            exact_job, _ = tier.submit(JobSpec.from_record({}))
            sketch_job, created = tier.submit(
                JobSpec.from_record({"pca_mode": "sketch"})
            )
            assert created and sketch_job.key != exact_job.key
            while tier.step(timeout=0.0):
                pass
            assert exact_job.state == "done", exact_job.error
            assert sketch_job.state == "done", sketch_job.error
            a = np.array([r[1:3] for r in exact_job.result], float)
            b = np.array([r[1:3] for r in sketch_job.result], float)
            assert np.abs(a - b).max() <= SKETCH_TOPK_ATOL
        finally:
            tier.close()


class TestSchemaDrift:
    """Both rejection directions for the sketch obs surface — the
    closed sets GL003 cross-checks statically."""

    def _trace(self, tmp_path, name):
        trace = tmp_path / "t.json"
        trace.write_text(
            json.dumps(
                {
                    "traceEvents": [
                        {
                            "ph": "X",
                            "name": name,
                            "pid": 1,
                            "ts": 0,
                            "dur": 1,
                        }
                    ]
                }
            )
        )
        return str(trace)

    @pytest.mark.parametrize(
        "name",
        [
            "gramian.sketch.accumulate",
            "gramian.sketch.window",
            "gramian.sketch.finish",
        ],
    )
    def test_sketch_spans_are_schema_known(self, tmp_path, name):
        assert validate.validate_trace(self._trace(tmp_path, name)) == []

    def test_unknown_sketch_span_rejected(self, tmp_path):
        errs = validate.validate_trace(
            self._trace(tmp_path, "gramian.sketch.carrier_sync")
        )
        assert errs and "gramian.sketch.carrier_sync" in errs[0]

    def test_windows_counter_requires_route_label(self, tmp_path):
        good = tmp_path / "good.prom"
        good.write_text('sketch_windows_total{route="scatter"} 3\n')
        assert validate.validate_metrics(str(good)) == []
        bad = tmp_path / "bad.prom"
        bad.write_text("sketch_windows_total 3\n")
        errs = validate.validate_metrics(str(bad))
        assert errs and "route" in errs[0]

    def test_schema_closed_set_is_the_emitted_set(self):
        assert validate._SKETCH_SPANS == {
            "gramian.sketch.accumulate",
            "gramian.sketch.window",
            "gramian.sketch.finish",
        }
        assert validate._LABELED_COUNTERS["sketch_windows_total"] == "route"

    def test_real_sketch_run_emits_schema_valid_artifacts(self, tmp_path):
        from spark_examples_tpu.obs import telemetry_session

        _, pair = structured_csr()
        trace = str(tmp_path / "sketch.trace.json")
        metrics = str(tmp_path / "sketch.prom")
        with telemetry_session(trace_out=trace, metrics_out=metrics):
            sketch_eig(_panel(pair, power_iters=1), K)
        assert validate.validate_trace(trace) == []
        assert validate.validate_metrics(metrics) == []
        evs = json.load(open(trace))["traceEvents"]
        emitted = {e.get("name") for e in evs if e.get("ph") == "X"}
        assert "gramian.sketch.accumulate" in emitted
        assert "gramian.sketch.window" in emitted
        assert "gramian.sketch.finish" in emitted


# ---------------------------------------------------------------- pod sim

import socket  # noqa: E402
import subprocess  # noqa: E402

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
    port = _free_port()
    env = {
        **os.environ,
        "PYTHONPATH": os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))
        ),
        "JAX_COORDINATOR_ADDRESS": f"127.0.0.1:{port}",
        "JAX_NUM_PROCESSES": str(n),
        "XLA_FLAGS": "--xla_force_host_platform_device_count=2",
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


_POD_SKETCH_WORKER = textwrap.dedent(
    """
    import json, os, sys
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    jax.config.update("jax_platforms", "cpu")
    import numpy as np
    from jax.sharding import Mesh

    from spark_examples_tpu.parallel.distributed import initialize_from_env
    assert initialize_from_env()
    from spark_examples_tpu.arrays.blocks import csr_windows
    from spark_examples_tpu.ops.sketch import sketch_eig
    from spark_examples_tpu.parallel.sharded import sharded_sketch_panel
    from spark_examples_tpu import obs

    pid, world = jax.process_index(), jax.process_count()
    mesh = Mesh(np.array(jax.devices()).reshape(world, 2), ("data", "model"))

    # The SAME structured 3-population cohort the host test derives
    # (structured_csr(36, 240, seed=0, pops=3), bit for bit).
    n, v, pops = 36, 240, 3
    rng = np.random.default_rng(0)
    labels = np.arange(n) % pops
    x = np.zeros((n, v), np.int8)
    for j in range(v):
        p_carry = np.where(labels == (j % pops), 0.85, 0.05)
        x[:, j] = rng.random(n) < p_carry
    cols, rows = np.nonzero(x.T)
    lens = np.bincount(cols, minlength=v)
    offsets = np.zeros(v + 1, np.int64)
    np.cumsum(lens, out=offsets[1:])
    pair = (rows.astype(np.int64), offsets)

    windows = list(csr_windows(iter([pair]), 32))
    mine = windows[pid::world]

    counter = obs.get_registry().counter(
        "sketch_windows_total",
        "CSR windows applied to the randomized sketch panel",
    )
    before = {
        r: counter.labels(route=r).value for r in ("scatter", "dense")
    }
    panel = sharded_sketch_panel(
        lambda: iter(mine), n, 2, mesh, power_iters=2, block_variants=32,
    )
    coords, vals = sketch_eig(panel, 2)
    after = {
        r: counter.labels(route=r).value for r in ("scatter", "dense")
    }
    if pid == 0:
        with open(sys.argv[1], "w") as f:
            json.dump(
                {
                    "coords": np.asarray(coords).tolist(),
                    "vals": np.asarray(vals).tolist(),
                    "row_sums": np.asarray(panel.row_sums).tolist(),
                    "n_padded": int(panel.y.shape[0]),
                    "host_peak_bytes": int(panel.host_peak_bytes),
                    "windows_delta": {
                        r: after[r] - before[r]
                        for r in ("scatter", "dense")
                    },
                    "my_windows": len(mine),
                },
                f,
            )
    """
)


@pod_skip
class TestPodSketchProtocol:
    """The sketch panel on a REAL 2-process ``jax.distributed`` CPU
    mesh: the collective accumulation over per-process window slices
    matches the meshless same-seed run and the exact spectrum."""

    def test_pod_sketch_matches_meshless_and_exact(self, tmp_path):
        nprocs = 2
        if nprocs * 2 > (os.cpu_count() or 1) * 4:
            pytest.skip("not enough cores to host the pod-sim")
        script = tmp_path / "worker.py"
        script.write_text(_POD_SKETCH_WORKER)
        out_file = tmp_path / "result.json"
        _run_pod_workers(script, [out_file], n=nprocs)
        result = json.loads(out_file.read_text())

        x, pair = structured_csr()
        got = np.asarray(result["coords"])
        got_vals = np.asarray(result["vals"])
        ref_c, ref_v = exact_eig(x)
        assert_spectrum(
            got, got_vals, ref_c, ref_v, SKETCH_TOPK_RTOL, SKETCH_TOPK_ATOL
        )
        single, single_vals = sketch_eig(_panel(pair), K)
        assert np.abs(got - single).max() <= 1e-4
        np.testing.assert_allclose(got_vals, single_vals, rtol=1e-5)
        # G's row sums survived the pod accumulation (parity print).
        np.testing.assert_allclose(
            np.asarray(result["row_sums"])[:N],
            (x.astype(np.float64) @ x.sum(0)).ravel(),
            rtol=1e-6,
        )
        # Every local window entered the protocol exactly once per
        # pass (3 passes: first + 2 power iterations), counted on the
        # lead process.
        assert (
            result["windows_delta"]["scatter"]
            + result["windows_delta"]["dense"]
            == 3 * result["my_windows"]
        )
        # Footprint: the pod panel is padded rows at the documented
        # O(N·l) bound (N² only loses at real scale, so pin the
        # formula, not an inequality that flips at toy N).
        assert result["n_padded"] % (nprocs * 2) == 0
        assert result["host_peak_bytes"] == sketch_host_bytes(
            result["n_padded"], randomized_panel_width(N, K)
        )
