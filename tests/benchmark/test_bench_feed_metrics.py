"""The readers that split the device's idle time by the host feed
(``feed_starved_s``, ``dispatch_blocked_s``), on hand-built traces with
answers worked by hand, on the small trace recorded on a TPU v5e before
the feed carried its spans, and in a traced run on the CPU."""

import gzip
import os
import shutil

import pytest

import bench_helpers as bh
from benchmark import intervals, run, trace

FIXTURE = os.path.join(bh.REPO, "tests", "benchmark", "data",
                       "kgp3_small.xplane.pb.gz")
PLANE = "/device:TPU:0"


def _reader(name):
    return run.load_module(bh.REPO, "metrics", name)


def _observed(tr=None, analyses=2):
    return run.Observed(analyses=analyses, compiles=0, work={}, peaks={},
                        trace=tr)


@pytest.fixture()
def hand_trace():
    """Window 0-10 s. The device runs 1-4 s (two overlapping ops) and
    8-10 s (an op that runs past the window), so it idles 0-1 and 4-8."""
    return trace.Trace(
        window=(0.0, 10.0),
        ops={PLANE: [("%a = x", 1.0, 3.0), ("%b = y", 2.0, 4.0),
                     ("%c = z", 8.0, 12.0)]},
        spans=[
            ("feed.wait", -1.0, 0.5),           # starts before the window
            ("feed.wait", 4.0, 5.0),
            ("feed.wait", 4.5, 6.0),            # overlaps the one before
            ("feed.wait", 7.0, 7.2),            # inside a dispatch span
            ("gramian.accumulate", 0.5, 2.0),
            ("gramian.accumulate", 5.0, 7.5),
            ("gramian.sparse.window", 9.5, 11.0),  # ends after the window
            ("ingest.fetch", 0.0, 10.0),        # neither family
        ])


def test_interval_algebra_by_hand():
    xs = [(0.0, 1.0), (4.0, 8.0)]
    ys = [(0.5, 2.0), (5.0, 7.5), (9.5, 10.0)]
    assert intervals.intersect(xs, ys) == [(0.5, 1.0), (5.0, 7.5)]
    assert intervals.subtract([(0.0, 10.0)], [(2.0, 3.0), (5.0, 6.0)]) == [
        (0.0, 2.0), (3.0, 5.0), (6.0, 10.0)]
    assert intervals.subtract([(0.0, 10.0), (11.0, 15.0)], [(8.0, 12.0)]) == [
        (0.0, 8.0), (12.0, 15.0)]
    assert intervals.subtract([(1.0, 2.0)], []) == [(1.0, 2.0)]
    assert intervals.seconds([(0.0, 1.5), (2.0, 2.5)]) == 2.0


def test_idle_and_span_union_by_hand(hand_trace):
    assert intervals.idle(hand_trace) == [(0.0, 1.0), (4.0, 8.0)]
    assert intervals.spans(hand_trace, ["feed.wait"]) == [
        (0.0, 0.5), (4.0, 6.0), (7.0, 7.2)]


def test_feed_starved_by_hand(hand_trace):
    # Idle 0-1 and 4-8 against waits 0-0.5, 4-6 and 7-7.2: 2.7 s in two
    # analyses.
    value = _reader("feed_starved_s").read(_observed(hand_trace))
    assert value == pytest.approx(2.7 / 2)


def test_dispatch_blocked_by_hand(hand_trace):
    # Idle under a dispatch span: 0.5-1 and 5-7.5 (the span at 9.5-10
    # meets a busy device). Less the waits 4-6 and 7-7.2: 0.5 + 1.3 s.
    value = _reader("dispatch_blocked_s").read(_observed(hand_trace))
    assert value == pytest.approx(1.8 / 2)


def test_starved_and_blocked_fit_in_the_idle_time(hand_trace):
    obs = _observed(hand_trace)
    total = (_reader("feed_starved_s").read(obs)
             + _reader("dispatch_blocked_s").read(obs)) * obs.analyses
    assert total <= intervals.seconds(intervals.idle(hand_trace))


@pytest.mark.parametrize("name", ["feed_starved_s", "dispatch_blocked_s"])
def test_device_readers_find_nothing_without_a_device(name, hand_trace):
    assert _reader(name).read(_observed(None)) is None
    no_device = trace.Trace(window=hand_trace.window,
                            spans=hand_trace.spans)
    assert _reader(name).read(_observed(no_device)) is None


def test_readers_find_nothing_without_their_spans(hand_trace):
    bare = trace.Trace(window=hand_trace.window, ops=hand_trace.ops,
                       spans=[("ingest.fetch", 0.0, 10.0)])
    assert _reader("feed_starved_s").read(_observed(bare)) is None
    assert _reader("dispatch_blocked_s").read(_observed(bare)) is None


def test_starved_description_by_hand(hand_trace):
    reader = _reader("feed_starved_s")
    assert reader.describe(_observed(hand_trace)) == (
        "device idle 2.5 s per analysis; 3 separate feed.wait intervals "
        "in the window")
    assert reader.describe(_observed(None)) == "no device plane"


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("trace") / "small.xplane.pb")
    with gzip.open(FIXTURE) as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return trace.load(path, {"feed.wait", "gramian.accumulate",
                             "gramian.sparse.window"})


def test_recorded_trace_without_the_feed_spans(recorded):
    """The recorded trace carries the dispatch spans but no ``feed.wait``:
    the starved reader finds nothing, the blocked one reads idle time
    under dispatch spans, no more than the trace's idle seconds."""
    names = {n for n, _, _ in recorded.spans}
    assert "feed.wait" not in names
    assert {"gramian.accumulate", "gramian.sparse.window"} <= names
    obs = _observed(recorded, analyses=2)
    assert _reader("feed_starved_s").read(obs) is None
    blocked = _reader("dispatch_blocked_s").read(obs)
    assert blocked is not None and blocked >= 0.0
    idle_s = recorded.window_s - trace.busy_seconds(recorded)
    assert blocked * obs.analyses <= idle_s


@pytest.mark.parametrize("cell", ["kgp3_chr20.auto", "nygc_chr20_rare.sparse",
                                  "nygc_chr20_rare_full.auto"])
def test_cpu_traced_run_leaves_the_device_readers_out(cell, tmp_path,
                                                      monkeypatch, capsys):
    """The CPU has no device plane: both readers find nothing there, and
    the line leaves them out rather than reporting 0."""
    bh.stub_chip(monkeypatch)
    code = run.main(["--workload", cell, "--seed", "3000000001",
                     "--seconds", "0.3", "--trace", "1"],
                    root=bh.tiny_root(tmp_path))
    out, err = capsys.readouterr()
    assert code == 0
    line = bh.last_json_line(out)
    assert line["correct"] is True
    assert not {"feed_starved_s", "dispatch_blocked_s"} & set(line["metrics"])
    assert "feed_starved_s: no device plane" in err
