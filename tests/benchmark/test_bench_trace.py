"""The trace reduction on a small trace recorded on a TPU v5e: two
analyses of a 2,504 x 40,000 cohort (one ``--pca-mode auto``, one
``--pca-mode sparse``, whose dense windows take the packed MXU route),
traced as run.py traces its window."""

import gzip
import os
import shutil

import pytest

import bench_helpers as bh
from benchmark import trace

FIXTURE = os.path.join(bh.REPO, "tests", "benchmark", "data",
                       "kgp3_small.xplane.pb.gz")
SPANS = {"ingest.fetch", "ingest.build", "ingest.put", "ingest.slice",
         "ingest.stream", "gramian.accumulate", "gramian_blockwise",
         "gramian.sparse.accumulate", "gramian.sparse.window",
         "fused_finish"}


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("trace") / "small.xplane.pb")
    with gzip.open(FIXTURE) as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return trace.load(path, SPANS)


def test_planes_window_and_spans(recorded):
    assert list(recorded.ops) == ["/device:TPU:0"]
    assert 0.4 < recorded.window_s < 0.7
    assert {name for name, _, _ in recorded.spans} <= SPANS
    assert {"ingest.fetch", "fused_finish"} <= {
        name for name, _, _ in recorded.spans}


def test_busy_union_and_idle_gaps_fill_the_window(recorded):
    busy = trace.busy_seconds(recorded)
    assert 0.0 < busy < recorded.window_s
    gaps = trace.idle_gaps(recorded, limit=100)
    assert sum(s for _, s in gaps) + busy == pytest.approx(
        recorded.window_s, rel=1e-9)
    labels = [label for label, _ in gaps]
    assert set(labels) <= SPANS | {"(no program span)"}
    # The host was fetching shards for most of the device's idle time.
    assert labels[0] == "ingest.fetch"
    assert [s for _, s in gaps] == sorted((s for _, s in gaps), reverse=True)


def test_per_executable_device_time(recorded):
    acc = trace.module_seconds(recorded, ["_gramian_accumulate_packed_jit"])
    fin = trace.module_seconds(recorded, ["_finish_jit"])
    assert acc > 0.0 and fin > 0.0
    assert acc + fin <= trace.busy_seconds(recorded)
    events = trace.clip(recorded.modules["/device:TPU:0"],
                               recorded.window)
    # 2 analyses x 5 packed blocks of 8,192 sites.
    assert sum("_gramian_accumulate_packed_jit" in n
               for n, _, _ in events) == 10
    assert sum("_finish_jit" in n for n, _, _ in events) == 2
    assert trace.module_seconds(recorded, ["_scatter_accumulate_jit"]) == 0.0


def test_top_ops_are_named_by_executable_and_op(recorded):
    ops = trace.top_ops(recorded)
    assert 0 < len(ops) <= 10
    name, seconds = ops[0]
    assert name == "jit__gramian_accumulate_packed_jit/fusion"
    assert seconds == pytest.approx(
        trace.module_seconds(recorded, ["_gramian_accumulate_packed_jit"]),
        rel=1e-3)
    assert all(" " not in n and n.count("/") == 1 for n, _ in ops)


def test_union_and_clip_by_hand():
    assert trace.union([(3, 4), (0, 1), (0.5, 2), (2, 2.5)]) == [
        (0, 2.5), (3, 4)]
    assert trace.clip([("a", 0, 2), ("b", 3, 5), ("c", 6, 7)], (1, 4)) == [
        ("a", 1, 2), ("b", 3, 4)]
    t = trace.Trace(window=(0.0, 10.0),
                    ops={"/device:TPU:0": [("%a = x", 1.0, 3.0),
                                           ("%b = y", 2.0, 4.0),
                                           ("%c = z", 8.0, 12.0)]},
                    spans=[("ingest.fetch", 0.0, 5.0),
                           ("ingest.build", 4.5, 9.0)])
    assert trace.busy_seconds(t) == 5.0
    assert trace.idle_gaps(t) == [("ingest.build", 4.0),
                                  ("ingest.fetch", 1.0)]


def test_missing_window_is_an_error(tmp_path, recorded):
    with pytest.raises(ValueError, match="annotation in the trace"):
        path = str(tmp_path / "x.xplane.pb")
        with gzip.open(FIXTURE) as src, open(path, "wb") as dst:
            shutil.copyfileobj(src, dst)
        original = trace.WINDOW
        try:
            trace.WINDOW = "no.such.window"
            trace.load(path)
        finally:
            trace.WINDOW = original


def _roofline(name, tr, chips, work):
    from benchmark import run
    from benchmark.peaks import DEVICE_PEAKS

    obs = run.Observed(analyses=2, compiles=0, work=work,
                       peaks=DEVICE_PEAKS["TPU v5 lite"], trace=tr,
                       chips=chips)
    return run.load_module(bh.REPO, "metrics", name).read(obs)


WORK = {"n": 2504, "v": 40000, "nnz": 4_960_000, "sum_k2": 5_520_000_000,
        "k": 2}


@pytest.mark.parametrize("name,want", [
    # Read with the one-chip formula before the per-chip least time.
    ("gramian_roofline", 2.577406827599223),
    ("finish_roofline", 1.733309151106175),
])
def test_one_chip_roofline_on_the_recorded_trace_is_unchanged(recorded, name,
                                                              want):
    assert _roofline(name, recorded, 1, WORK) == want


@pytest.mark.parametrize("name,kernel", [
    ("gramian_roofline", "jit__gramian_accumulate_packed_jit(3)"),
    ("finish_roofline", "jit__finish_jit(7)"),
])
def test_four_planes_read_the_share_of_one(name, kernel):
    """The same work split over four chips, each running its quarter in a
    quarter of the time, reads the share one chip reads doing it all."""
    def planes(count, seconds):
        return {f"/device:TPU:{i}": [(kernel, 1.0, 1.0 + seconds)]
                for i in range(count)}

    one = trace.Trace(window=(0.0, 10.0), modules=planes(1, 0.8))
    four = trace.Trace(window=(0.0, 10.0), modules=planes(4, 0.2))
    share = _roofline(name, one, 1, WORK)
    assert share is not None and share > 0.0
    assert _roofline(name, four, 4, WORK) == pytest.approx(share, rel=1e-12)
    # Read as one chip, the four planes' share would be four times too high.
    assert _roofline(name, four, 1, WORK) == pytest.approx(4 * share)
