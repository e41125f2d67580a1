"""The device feed's spans and counter: the dispatching thread's wait for
its next input (``feed.wait``), the put that ends when the staged array is
ready (``ingest.put`` with ``feed_put_bytes_total``), and the CSR
sidecar's open (``ingest.sidecar_open``), once per source."""

import os
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pytest

from spark_examples_tpu import obs
from spark_examples_tpu.arrays import feed


def _events(session, name):
    return [ev for ev in session.tracer.to_chrome()["traceEvents"]
            if ev.get("ph") == "X" and ev["name"] == name]


def _blocks(n, shape=(4, 16)):
    return [np.full(shape, i, dtype=np.uint8) for i in range(n)]


def test_one_wait_per_item_on_the_consumer_thread():
    blocks = _blocks(5)
    with obs.TelemetrySession(annotate_jax=False) as session:
        got = [np.asarray(x) for x in feed.device_prefetch(iter(blocks))]
    assert [int(x[0, 0]) for x in got] == list(range(5))
    waits = _events(session, "feed.wait")
    # One per item, and the last pull, which finds the end of the stream.
    assert len(waits) == len(blocks) + 1
    assert {ev["tid"] for ev in waits} == {threading.get_ident()}
    # The puts run on the producer thread, not the dispatching one.
    puts = _events(session, "ingest.put")
    assert len(puts) == len(blocks)
    assert threading.get_ident() not in {ev["tid"] for ev in puts}


class _Staged:
    """A staged array whose transfer takes ``delay`` seconds to land."""

    ready_at = []

    def __init__(self, arr, delay):
        self.arr, self.delay = arr, delay

    def block_until_ready(self):
        time.sleep(self.delay)
        _Staged.ready_at.append(time.perf_counter())
        return self


def test_put_ends_when_the_staged_array_is_ready(monkeypatch):
    _Staged.ready_at = []
    monkeypatch.setattr(feed.jax, "device_put",
                        lambda arr, *target: _Staged(arr, 0.02))
    with obs.TelemetrySession(annotate_jax=False) as session:
        got = list(feed.device_prefetch(iter(_blocks(3))))
    assert len(got) == 3 and len(_Staged.ready_at) == 3
    t0 = session.tracer._t0
    ends = sorted(t0 + (ev["ts"] + ev["dur"]) * 1e-6
                  for ev in _events(session, "ingest.put"))
    assert len(ends) == 3
    for end, ready in zip(ends, sorted(_Staged.ready_at)):
        assert end >= ready
    assert all(ev["dur"] >= 0.02 * 1e6
               for ev in _events(session, "ingest.put"))


def test_put_bytes_counter_counts_the_bytes_fed():
    blocks = _blocks(4, shape=(8, 32)) + [np.zeros((3, 5), np.int32)]
    with obs.TelemetrySession(annotate_jax=False) as session:
        list(feed.device_prefetch(iter(blocks)))
    counters = session.registry.snapshot()["counters"]
    assert counters["feed_put_bytes_total"] == sum(b.nbytes for b in blocks)


def test_sparse_accumulation_waits_once_per_window():
    from spark_examples_tpu.ops.sparse import sparse_gramian_blockwise

    n = 8
    windows = [
        (np.array([0, 1, 2, 3, 4], np.int32), np.array([3, 2])),
        (np.array([5, 6, 7], np.int32), np.array([1, 2])),
        (np.array([0, 7], np.int32), np.array([2])),
    ]
    want = np.zeros((n, n))
    for idx, lens in windows:
        for site in np.split(idx, np.cumsum(lens)[:-1]):
            want[np.ix_(site, site)] += 1
    with obs.TelemetrySession(annotate_jax=False) as session:
        g = sparse_gramian_blockwise(iter(windows), n)
    np.testing.assert_array_equal(np.asarray(g), want)
    waits = _events(session, "feed.wait")
    assert len(waits) == len(windows) + 1
    assert len(_events(session, "gramian.sparse.window")) == len(windows)
    assert {ev["tid"] for ev in waits} == {threading.get_ident()}


@pytest.fixture()
def light_mirror(tmp_path):
    """A cohort on disk turned into a light mirror: its sidecar, built
    by a first source, and the mirror's markers; no variants file."""
    from spark_examples_tpu.genomics import sources
    from spark_examples_tpu.genomics.fixtures import synthetic_cohort
    from spark_examples_tpu.genomics.shards import Shard

    root = str(tmp_path / "cohort")
    synthetic_cohort(12, 90, seed=3).dump(root)
    builder = sources.JsonlSource(root)
    with obs.TelemetrySession(annotate_jax=False) as session:
        builder.stream_carrying_csr(
            "", Shard("17", 41196311, 41277499), _index(builder), None)
    assert len(_events(session, "ingest.sidecar_open")) == 1
    assert os.path.exists(os.path.join(root, sources.SIDECAR_BASENAME))
    for name in os.listdir(root):
        if name.startswith("variants.jsonl"):
            os.unlink(os.path.join(root, name))
    for name, text in ((sources.MIRROR_COMPLETE_MARKER, ""),
                       (sources.MIRROR_IDENTITY_FILE, "mirror-1"),
                       (sources.MIRROR_SIDECAR_OK, "mirror-1")):
        with open(os.path.join(root, name), "w") as f:
            f.write(text)
    return root


def _index(source):
    return {c.id: i for i, c in enumerate(source.list_callsets(""))}


def test_sidecar_opens_once_per_source_however_many_shards(light_mirror):
    from spark_examples_tpu.genomics.shards import Shard
    from spark_examples_tpu.genomics.sources import JsonlSource

    lo, hi = 41196311, 41277499
    edges = np.linspace(lo, hi, 9).astype(int)
    shards = [Shard("17", int(a), int(b))
              for a, b in zip(edges[:-1], edges[1:])]
    with obs.TelemetrySession(annotate_jax=False) as session:
        for _ in range(2):
            source = JsonlSource(light_mirror)
            index = _index(source)
            with ThreadPoolExecutor(4) as pool:
                got = list(pool.map(
                    lambda s: source.stream_carrying_csr("", s, index, None),
                    shards))
            assert sum(r is not None for r in got) > 1
    opens = _events(session, "ingest.sidecar_open")
    assert len(opens) == 2  # one per source


def test_sidecar_open_span_carries_the_checks_bytes_and_threads(
        light_mirror, monkeypatch):
    """The open's args: every member's bytes CRC-checked, and the most
    threads one member's check took (its chunks, here with chunks small
    enough that the larger members split)."""
    import zipfile

    from spark_examples_tpu.genomics import sources
    from spark_examples_tpu.genomics.shards import Shard

    chunk = 512
    monkeypatch.setattr(sources, "_CRC_CHUNK_BYTES", chunk)
    monkeypatch.setattr(sources, "_crc_threads", lambda: 4)
    side = os.path.join(light_mirror, sources.SIDECAR_BASENAME)
    with zipfile.ZipFile(side) as zf:
        sizes = [i.file_size for i in zf.infolist()]
    assert max(sizes) >= 2 * chunk
    source = sources.JsonlSource(light_mirror)
    with obs.TelemetrySession(annotate_jax=False) as session:
        source.stream_carrying_csr(
            "", Shard("17", 41196311, 41277499), _index(source), None)
    (opened,) = _events(session, "ingest.sidecar_open")
    assert opened["args"]["bytes"] == sum(sizes)
    assert opened["args"]["threads"] == max(
        min(4, size // chunk) for size in sizes)


def test_feed_spans_stay_out_of_the_ingest_family():
    """``ingest_wall_s`` unions every ``ingest.*`` span: the dispatcher's
    waits must not be counted as ingest."""
    from tools.graftlint.rules import span_contract

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    schema = span_contract.load_schema(repo)
    assert schema._FEED_SPANS
    assert not any(n.startswith("ingest.") for n in schema._FEED_SPANS)
    assert all(n.startswith("feed.") for n in schema._FEED_SPANS)
    assert "ingest.sidecar_open" in schema._INGEST_SPANS
