"""The exact mesh PCoA at biobank N, at CPU size: VariantsPcaDriver's own mesh
from ``--mesh-shape``, the per-device footprint bound, the int8 MXU tile
update of the host-local mesh stream, and the float32 mesh finish — each
against the scan path or the plain float64 reference of
``benchmark/reference.py``."""

import json
import os
import shutil
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from spark_examples_tpu import obs
from spark_examples_tpu.arrays.blocks import csr_windows
from spark_examples_tpu.genomics.fixtures import (
    DEFAULT_VARIANT_SET_ID,
    synthetic_cohort,
)
from spark_examples_tpu.models.pca import VariantsPcaDriver
from spark_examples_tpu.ops.sparse import (
    carrier_counts_fit_int8,
    carrier_pairs,
)
from spark_examples_tpu.parallel.mesh import make_mesh
from spark_examples_tpu.parallel.sharded import (
    sharded_pcoa,
    sparse_sharded_gramian_blockwise,
)
from spark_examples_tpu.utils.config import PcaConfig

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark import reference, run  # noqa: E402
from benchmark.cohortlib import write_light_mirror  # noqa: E402
from benchmark.cohorts import structured_sites  # noqa: E402

PATHS = "sparse_scatter_path_windows_total"


def _paths():
    counter = obs.get_registry().counter(PATHS)
    return {p: counter.labels(path=p).value for p in ("mxu-tile", "scan")}


def _driver(mesh_shape=None, mesh=None, **conf_kw):
    conf = PcaConfig(variant_set_ids=[DEFAULT_VARIANT_SET_ID],
                     block_variants=32, mesh_shape=mesh_shape, **conf_kw)
    return VariantsPcaDriver(conf, synthetic_cohort(12, 90), mesh=mesh)


class TestDriverMesh:
    def test_driver_builds_its_mesh_from_the_config(self):
        driver = _driver("data:2,model:2")
        assert dict(driver.mesh.shape) == {"data": 2, "model": 2}
        # Equal to a mesh built apart over the same devices and axes,
        # so executables cached on it hit for the next driver.
        assert driver.mesh == make_mesh("data:2,model:2")
        assert _driver().mesh is None

    def test_an_explicit_mesh_wins(self):
        mesh = make_mesh("data:4")
        assert _driver("data:2,model:2", mesh=mesh).mesh is mesh


class TestPerDeviceBound:
    def test_auto_stays_exact_on_the_mesh_and_sketches_meshless(
        self, monkeypatch
    ):
        """With the budget at one 2x2 tile's bytes, auto on the mesh
        takes the exact sparse engine; meshless, where one device would
        hold all of G, it takes the sketch route."""
        monkeypatch.setattr(
            VariantsPcaDriver, "SKETCH_AUTO_G_BYTES", 4 * 6 * 6
        )
        meshed = _driver("data:2,model:2", sample_shard_threshold=8)
        meshless = _driver(sample_shard_threshold=8)
        assert meshed._sparse_device_g_bytes() == 4 * 6 * 6
        assert not meshed.sketch_selected()
        assert meshed._sparse_selected()
        assert meshless.sketch_selected()
        calls = list(meshed.get_calls(meshed.get_data()))
        out = meshed.get_similarity_matrix_stream(
            iter(calls), max_device_bytes=4 * 6 * 6
        )
        assert out.shape == (12, 12)


class TestCarrierPairs:
    def test_pairs_pad_to_a_power_of_two(self):
        idx = np.array([5, 7, 2, 9, 9, 9], dtype=np.int64)
        lens = np.array([2, 1, 0, 3], dtype=np.int64)
        pairs = carrier_pairs(idx, lens, sentinel=10)
        assert pairs.shape == (2, 8) and pairs.dtype == np.int32
        np.testing.assert_array_equal(pairs[0, :6], idx)
        np.testing.assert_array_equal(pairs[1, :6], [0, 0, 1, 3, 3, 3])
        assert (pairs[0, 6:] == 10).all()

    @pytest.mark.parametrize("repeats,fits", [
        (0, True), (1, True), (127, True), (128, False),
    ])
    def test_int8_check_counts_repeats_at_large_sites(self, repeats, fits):
        rng = np.random.default_rng(repeats)
        lens = np.array([3, 200, 1, 300], dtype=np.int64)
        idx = np.concatenate(
            [rng.choice(1000, size=k, replace=False) for k in lens]
        )
        start = 3 + 200 + 1
        idx[start:start + repeats] = 17  # one carrier, `repeats` times
        assert carrier_counts_fit_int8(idx, lens, 1000) is fits

    def test_small_sites_fit_whatever_repeats(self):
        lens = np.array([127, 5], dtype=np.int64)
        idx = np.full(132, 4, dtype=np.int64)
        assert carrier_counts_fit_int8(idx, lens, 10)


def _window_case(case, n):
    """(indices, offsets, dense X) for one tile-update case."""
    rng = np.random.default_rng(len(case))
    v = {"tail": 300, "mixed": 256}.get(case, 256)
    lens = rng.integers(1, 4, size=v)
    lens[::37] = 1
    lens[5] = 150
    lens[77] = 220
    idx = np.concatenate(
        [rng.choice(n, size=k, replace=False) for k in lens]
    ).astype(np.int64)
    offsets = np.concatenate([[0], np.cumsum(lens)])
    if case == "repeat":
        idx[offsets[5]:offsets[5] + 5] = idx[offsets[5]]
    elif case == "overflow":
        idx[offsets[77]:offsets[77] + 130] = idx[offsets[77]]
    x = np.zeros((n, v), np.int64)
    np.add.at(x, (idx, np.repeat(np.arange(v), lens)), 1)
    return idx, offsets, x


class TestTileMxuUpdate:
    """The host-local mesh stream's int8 MXU tile update, forced on the
    CPU by letting the path rule see a TPU: bit-identical to the scan on
    windows that mix single carriers with sites past int8's 127, a
    carrier repeated at one site, and a short tail window; a site whose
    repeats pass 127 keeps the scan for its window."""

    @pytest.mark.parametrize("spec", ["data:2,model:2", "data:2,model:1"])
    @pytest.mark.parametrize("case", ["mixed", "repeat", "tail",
                                      "overflow"])
    def test_matches_scan_and_numpy(self, monkeypatch, spec, case):
        n = 301  # not a multiple of the grid: padded tiles
        idx, offsets, x = _window_case(case, n)
        want = (x @ x.T).astype(np.float32)
        mesh = make_mesh(spec)

        def accumulate():
            # Every window on the scatter route, whatever its density.
            return np.asarray(sparse_sharded_gramian_blockwise(
                csr_windows(iter([(idx, offsets)]), 128), n, mesh,
                density_threshold=0.99, block_variants=128,
            ))

        scan = accumulate()
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        before = _paths()
        mxu = accumulate()
        after = _paths()
        np.testing.assert_array_equal(scan, want)
        np.testing.assert_array_equal(mxu, want)
        windows = -(-x.shape[1] // 128)
        fell = 1 if case == "overflow" else 0
        assert after["mxu-tile"] - before["mxu-tile"] == windows - fell
        assert after["scan"] - before["scan"] == fell


def _ukb_cohort(n_eur=400, others=100, sites=20000, rare_max=300):
    """The ukb50k_exome_rare cohort's classes at N = n_eur + 4 * others."""
    with open(os.path.join(REPO, "benchmark", "configs",
                           "ukb50k_exome_rare.json")) as f:
        cfg = json.load(f)["cohort"]
    cfg["sites"] = sites
    cfg["populations"] = [[p, n_eur if p == "EUR" else others]
                          for p, _ in cfg["populations"]]
    for cls in cfg["classes"]:
        cls["max_carriers"] = min(cls["max_carriers"], rare_max)
    return cfg


class _CompileCount:
    def __init__(self):
        self.count = 0

        def listen(event, duration, **_):
            if event == run.COMPILE_EVENT:
                self.count += 1

        jax.monitoring.register_event_duration_secs_listener(listen)


class TestMeshFinish:
    def test_float32_finish_matches_the_float64_reference(self):
        """Above a lowered dense-eigh limit (N = 600 > 256) the mesh
        finish's subspace iteration at float32 precision gives the
        reference's float64 coordinates to 1e-6, and a second call
        traces and compiles nothing."""
        cohort = structured_sites.generate(
            _ukb_cohort(n_eur=200, others=100, sites=8000, rare_max=150),
            7,
        )
        g_ref = reference.gramian(cohort)
        want = reference.coordinates(g_ref, 2)
        mesh = make_mesh("data:2,model:2")
        g = jax.device_put(
            jnp.asarray(g_ref),
            jax.sharding.NamedSharding(
                mesh, jax.sharding.PartitionSpec("data", "model")),
        )
        coords, _ = sharded_pcoa(g, 2, mesh, dense_eigh_limit=256)
        assert reference.coord_err(np.asarray(coords), want[:, :2]) < 1e-6
        clock = _CompileCount()
        again, _ = sharded_pcoa(g, 2, mesh, dense_eigh_limit=256)
        assert clock.count == 0
        np.testing.assert_array_equal(np.asarray(again), np.asarray(coords))


def test_harness_analysis_on_four_devices(tmp_path, monkeypatch):
    """``benchmark/run.analysis`` with the cell's flags on a scaled-down
    ukb50k_exome_rare cohort (the same classes, N = 800, V = 20,000) over
    a 2x2 mesh of CPU devices: every window on the MXU tile update, G
    exact and the coordinates within 1e-6 of the float64 reference. The
    sample-shard and dense-eigh limits are lowered to this N, and the
    density gate raised so that, as at N = 49,960 (at most 999 carriers,
    under 2% of the samples), every window takes the scatter route."""
    cohort = structured_sites.generate(_ukb_cohort(), 2**33 + 5)
    cohort_dir = str(tmp_path / "cohort")
    write_light_mirror(cohort, cohort_dir)
    with open(os.path.join(REPO, "benchmark", "traffic", "mesh.json")) as f:
        flags = json.load(f)["flags"] + [
            "--sample-sharded", "--dense-eigh-limit", "256",
            "--sparse-density-threshold", "0.5"]
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    before = _paths()
    try:
        g, coords, rows = run.analysis(cohort_dir, cohort.references(),
                                       flags)
    finally:
        shutil.rmtree(cohort_dir, ignore_errors=True)
    after = _paths()
    assert len(g.sharding.device_set) == 4
    assert after["mxu-tile"] > before["mxu-tile"]
    assert after["scan"] == before["scan"]
    checks = run.compare(cohort, [(np.asarray(g), coords, rows)], 2,
                         {"g_err": 0.0, "coord_err": 1e-6})
    assert checks["g_err"]["value"] == 0.0
    assert checks["coord_err"]["value"] < 1e-6
