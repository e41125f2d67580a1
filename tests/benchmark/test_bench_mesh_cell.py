"""The four-chip cell ``ukb50k_exome_rare.mesh``: how it resolves, and
its per-layer metrics read from a hand-made four-plane trace with answers
worked by hand."""

import pytest

import bench_helpers as bh
from benchmark import run, trace, work
from benchmark.peaks import DEVICE_PEAKS

CELL = "ukb50k_exome_rare.mesh"
V5E = DEVICE_PEAKS["TPU v5 lite"]
# The cohort's counts at the configuration's size (N, V, carriers, Σk²).
WORK = {"n": 49960, "v": 1_000_000, "nnz": 24_989_884,
        "sum_k2": 4_260_000_000, "k": 2}
PLANES = [f"/device:TPU:{i}" for i in range(4)]


def test_cell_resolves_on_four_chips():
    cell = run.resolve_cell(bh.REPO, CELL)
    assert cell.chips == 4
    assert cell.traffic["flags"] == [
        "--mesh-shape", "data:2,model:2", "--pca-mode", "auto"]
    cohort = cell.config["cohort"]
    assert sum(n for _, n in cohort["populations"]) == 49960
    assert cohort["sites"] == 1_000_000
    assert cell.config["limits"]["g_err"] == 0.0
    names = {m["name"] for m in cell.per_layer}
    assert names == {
        "compiles_in_window", "ingest_wall_s", "device_idle_share",
        "feed_starved_s", "dispatch_blocked_s", "mesh_gramian_roofline",
        "mesh_finish_roofline",
    }
    assert {m["name"] for m in cell.end_to_end} == {"analysis_s",
                                                     "setup_s"}


def test_config_lists_what_it_cut():
    spec = run.load_spec(bh.REPO)
    entry = run.find(spec["configs"], "ukb50k_exome_rare", "config")
    cfg = bh.load_config("ukb50k_exome_rare")
    assert entry["reduced"] == cfg["reduced"] == ["sites"]
    assert cfg["published"]["sites"] > cfg["cohort"]["sites"]


@pytest.fixture()
def four_planes():
    """Window 0-10 s over two analyses. Every chip runs the tile update
    for 3 s (1-4 s) and the finish for 0.1 s (8-8.1 s: centring 0.02 s,
    eigensolve 0.08 s); chip 0's host waits on the feed 4-5 s."""
    modules, ops = {}, {}
    for plane in PLANES:
        modules[plane] = [
            ("jit_mesh_tile_mxu(3)", 1.0, 2.5),
            ("jit_mesh_tile_mxu(3)", 2.5, 4.0),
            ("jit_mesh_center(5)", 8.0, 8.02),
            ("jit_mesh_subspace_eig_adaptive(6)", 8.02, 8.1),
        ]
        ops[plane] = [("%dot = s32[] convolution(a, b)", 1.0, 4.0),
                      ("%fusion = f32[] fusion(c)", 8.0, 8.1)]
    spans = [("feed.wait", 4.0, 5.0), ("gramian.sparse.window", 5.0, 6.0),
             ("finish.mesh.center", 8.0, 8.02),
             ("finish.mesh.eig", 8.02, 8.1)]
    return trace.Trace(window=(0.0, 10.0), ops=ops, modules=modules,
                       spans=spans)


def _read(tr, analyses=2):
    cell = run.resolve_cell(bh.REPO, CELL)
    obs = run.Observed(
        analyses=analyses, compiles=0, work=WORK, peaks=V5E,
        spans=[("ingest.fetch", 0.0, 0.5, 1), ("ingest.put", 0.25, 1.0, 2)],
        trace=tr, chips=cell.chips)
    out = {}
    for m in cell.per_layer:
        value = run.load_module(bh.REPO, "metrics", m["name"]).read(obs)
        if value is not None:
            out[m["name"]] = value
    return out


def test_per_layer_metrics_from_four_planes(four_planes):
    got = _read(four_planes)
    gram = work.gramian_least(WORK["n"], WORK["v"], WORK["nnz"],
                              WORK["sum_k2"], V5E, 4)
    fin = work.finish_least(WORK["n"], WORK["k"], V5E, 4)
    # 3 s of tile programs and 0.1 s of finish on each chip, two analyses.
    assert got["mesh_gramian_roofline"] == pytest.approx(
        100.0 * gram["seconds"] / 1.5)
    assert got["mesh_finish_roofline"] == pytest.approx(
        100.0 * fin["seconds"] / 0.05)
    assert gram["bound"] == "memory" and fin["bound"] == "memory"
    assert 0.0 < got["mesh_gramian_roofline"] < 100.0
    assert 0.0 < got["mesh_finish_roofline"] < 100.0
    assert got["device_idle_share"] == pytest.approx(100.0 * 6.9 / 10.0)
    assert got["feed_starved_s"] == pytest.approx(0.5)
    assert got["dispatch_blocked_s"] == pytest.approx(0.5)
    assert got["ingest_wall_s"] == pytest.approx(0.5)
    assert got["compiles_in_window"] == 0.0


def test_mesh_rooflines_find_nothing_without_the_mesh_programs(four_planes):
    """A program without the mesh programs (the parent's meshless route)
    leaves both mesh metrics out, and nothing raises."""
    bare = trace.Trace(
        window=four_planes.window, ops=four_planes.ops,
        modules={p: [("jit__finish_jit(7)", 8.0, 8.1)] for p in PLANES},
        spans=four_planes.spans)
    got = _read(bare)
    assert "mesh_gramian_roofline" not in got
    assert "mesh_finish_roofline" not in got
    assert "device_idle_share" in got
