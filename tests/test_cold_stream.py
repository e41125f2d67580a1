"""Cold-path streaming ingest: overlap fetch → decode → build → put.

Pins the cold-stream tier end to end (ISSUE 9 / ROADMAP item 3):

- mirror durability: every mirror file commits tmp → fsync → atomic
  rename (the ``mirror.write`` torn seam proves a kill -9 mid-write can
  never land a torn file at a committed name), and the deterministic
  staging dir means a restarted cold run REUSES a killed run's partial
  mirror instead of re-downloading it;
- the cold-stream tier itself: with an empty ``--cache-dir`` the source
  streams wire frames immediately (no mirror barrier) while the mirror
  downloads write-through in the background; ``--no-cold-stream``
  restores the phased path; G is bit-identical across cold-stream vs
  phased, worker counts, and shard arrival orders;
- the ``ingest.stream`` fault seam: mid-pipeline stall/error/truncate
  retries per ``--shard-retries`` to a bit-identical G, and fails
  loudly with retries off (GL005 discipline);
- observability: ``ingest.fetch``/``ingest.stream`` spans and the
  ``cold_stream_shards_total{stage}`` counter, schema-checked by
  ``scripts/validate_trace.py`` (closed sets, both directions);
- the loopback cold acceptance: against a latency-shaped server the
  streaming cold path beats the phased cold path >= 2x, and the first
  ``gramian.accumulate`` span begins before the last ``ingest.fetch``
  span ends — the device really does start before the last shard is
  off the wire.
"""

import importlib.util
import json
import os
import shutil
import time

import numpy as np
import pytest

from spark_examples_tpu.genomics.fixtures import (
    DEFAULT_VARIANT_SET_ID,
    synthetic_cohort,
)
from spark_examples_tpu.genomics.mirror import ColdStreamMirror
from spark_examples_tpu.genomics.service import (
    GenomicsServiceServer,
    HttpVariantSource,
)
from spark_examples_tpu.genomics.shards import shards_for_references
from spark_examples_tpu.genomics.sources import (
    MIRROR_COMPLETE_MARKER,
    SIDECAR_BASENAME,
    JsonlSource,
)
from spark_examples_tpu.models.pca import VariantsPcaDriver
from spark_examples_tpu.resilience import (
    FaultPlan,
    FaultRule,
    faults,
)
from spark_examples_tpu.utils.config import PcaConfig

REFS = "17:41196311:41277499"
VSID = DEFAULT_VARIANT_SET_ID


def _load_validate_trace():
    spec = importlib.util.spec_from_file_location(
        "validate_trace",
        os.path.join(
            os.path.dirname(os.path.dirname(__file__)),
            "scripts",
            "validate_trace.py",
        ),
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def cohort_dir(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("coldstream") / "cohort")
    synthetic_cohort(50, 400, references=REFS, seed=21).dump(root)
    src = JsonlSource(root)
    src.ensure_serving_index()  # sidecar + line index warm for serving
    return root


@pytest.fixture()
def served(cohort_dir):
    server = GenomicsServiceServer(JsonlSource(cohort_dir)).start()
    try:
        yield cohort_dir, f"http://127.0.0.1:{server.port}"
    finally:
        server.stop()


def _driver(source, **overrides):
    overrides.setdefault("ingest_workers", 2)
    conf = PcaConfig(
        references=REFS,
        variant_set_ids=[VSID],
        bases_per_partition=15_000,
        block_variants=64,
        **overrides,
    )
    return VariantsPcaDriver(conf, source)


def _gramian(source, **overrides):
    drv = _driver(source, **overrides)
    return np.asarray(drv.get_similarity_matrix_csr(drv.get_csr_fused()))


def _staging_dir(cache, mode="full"):
    entries = [
        e for e in os.listdir(cache) if e.startswith(".staging-cohort-")
    ]
    assert len(entries) <= 1, entries
    return os.path.join(cache, entries[0]) if entries else None


def _mirror_root(cache):
    entries = [e for e in os.listdir(cache) if e.startswith("cohort-")]
    return os.path.join(cache, entries[0]) if entries else None


class TestMirrorDurability:
    """Satellite: tmp-then-atomic-rename with fsync at every mirror
    write, pinned with the mirror.write torn seam, plus the
    restart-reuses-partial-mirror contract."""

    def test_torn_write_never_lands_and_restart_heals(
        self, served, tmp_path
    ):
        root, url = served
        cache = str(tmp_path / "cache")
        plan = FaultPlan(
            seed=1,
            rules=[
                FaultRule(
                    site="mirror.write",
                    kind="torn",
                    match="variants.jsonl",
                    times=1,
                )
            ],
        )
        src = HttpVariantSource(url, cache_dir=cache, cold_stream=False)
        with faults.active_plan(plan):
            with pytest.raises(IOError):
                src.list_callsets(VSID)
        assert plan.fired_total == 1
        # The torn write landed nowhere a reader trusts: no completed
        # mirror, no committed variants.jsonl — only a *.tmp-* partial
        # in the staging dir (exactly what a kill -9 mid-write leaves).
        assert _mirror_root(cache) is None
        staging = _staging_dir(cache)
        assert staging is not None
        assert not os.path.exists(os.path.join(staging, "variants.jsonl"))
        assert any(".tmp-" in e for e in os.listdir(staging))
        # callsets.json committed BEFORE the fault is whole and kept.
        assert os.path.exists(os.path.join(staging, "callsets.json"))
        # Restart (same cache, no plan): the download completes and the
        # mirror is byte-identical to one downloaded with no fault.
        src2 = HttpVariantSource(url, cache_dir=cache, cold_stream=False)
        src2.list_callsets(VSID)
        healed = _mirror_root(cache)
        assert healed is not None
        assert os.path.exists(
            os.path.join(healed, MIRROR_COMPLETE_MARKER)
        )
        clean_cache = str(tmp_path / "clean")
        HttpVariantSource(
            url, cache_dir=clean_cache, cold_stream=False
        ).list_callsets(VSID)
        clean = _mirror_root(clean_cache)
        for name in ("callsets.json", "variants.jsonl", SIDECAR_BASENAME):
            with open(os.path.join(healed, name), "rb") as a, open(
                os.path.join(clean, name), "rb"
            ) as b:
                assert a.read() == b.read(), name

    def test_restart_reuses_partial_mirror(self, cohort_dir, tmp_path):
        class _CountingExports:
            def __init__(self, inner):
                self._inner = inner
                self.exports = {}

            def export_lines(self, name):
                self.exports[name] = self.exports.get(name, 0) + 1
                return self._inner.export_lines(name)

            def __getattr__(self, name):
                return getattr(self._inner, name)

        counting = _CountingExports(JsonlSource(cohort_dir))
        server = GenomicsServiceServer(counting).start()
        cache = str(tmp_path / "cache")
        try:
            url = f"http://127.0.0.1:{server.port}"
            # Run 1 dies mid-download: callsets.json commits, then the
            # variants.jsonl write errors (a worker death / kill).
            plan = FaultPlan(
                seed=1,
                rules=[
                    FaultRule(
                        site="mirror.write",
                        kind="error",
                        match="variants.jsonl",
                        times=1,
                    )
                ],
            )
            with faults.active_plan(plan):
                with pytest.raises(IOError):
                    HttpVariantSource(
                        url, cache_dir=cache, cold_stream=False
                    ).list_callsets(VSID)
            assert counting.exports.get("callsets.json") == 1
            # Run 2 reuses the staged callsets.json: the export is NOT
            # re-fetched; only the missing files are.
            src = HttpVariantSource(
                url, cache_dir=cache, cold_stream=False
            )
            callsets = src.list_callsets(VSID)
            assert counting.exports.get("callsets.json") == 1  # reused
            assert counting.exports.get("variants.jsonl") == 2
            mirror = _mirror_root(cache)
            assert mirror is not None and os.path.exists(
                os.path.join(mirror, MIRROR_COMPLETE_MARKER)
            )
            # The healed mirror actually serves (parity with the truth).
            want = [c.id for c in JsonlSource(cohort_dir).list_callsets(VSID)]
            assert [c.id for c in callsets] == want
        finally:
            server.stop()

    def test_concurrent_populate_never_touches_live_peers_staging(
        self, served, tmp_path
    ):
        """Two processes cold on the same cache/identity: the shared
        deterministic staging is serialized by a pid lock, and a LIVE
        peer's lock routes this populate into an isolated one-shot dir
        — the peer's in-flight files are never swept, and losing the
        populate race is still success."""
        root, url = served
        cache = str(tmp_path / "cache")
        os.makedirs(cache)
        ident = JsonlSource(root).cohort_identity()
        lock = os.path.join(cache, f".lock-cohort-{ident}-full")
        with open(lock, "w") as f:
            f.write(str(os.getpid()))  # a LIVE peer holds the lock
        shared = os.path.join(cache, f".staging-cohort-{ident}-full")
        os.makedirs(shared)
        peer_tmp = os.path.join(shared, f"variants.jsonl.tmp-{os.getpid()}")
        with open(peer_tmp, "w") as f:
            f.write("peer in-flight bytes")
        src = HttpVariantSource(url, cache_dir=cache, cold_stream=False)
        got = [c.id for c in src.list_callsets(VSID)]
        want = [c.id for c in JsonlSource(root).list_callsets(VSID)]
        assert got == want  # populated via the isolated path
        assert os.path.exists(
            os.path.join(_mirror_root(cache), MIRROR_COMPLETE_MARKER)
        )
        # The live peer's staging and in-flight tmp were never touched.
        assert os.path.exists(peer_tmp)
        assert os.path.exists(lock)
        os.unlink(lock)

    def test_prune_spares_live_foreign_staging_reaps_dead_one(
        self, served, tmp_path
    ):
        """Post-download pruning of OTHER identities' staging dirs must
        consult their pid locks: in a shared cache_dir two different
        cohorts may mirror concurrently (HTTP and gRPC sources share
        caches), and a live peer's in-flight staging must survive a
        sibling's successful download — while a dead run's foreign
        staging is still reaped so cache_dir stays bounded."""
        root, url = served
        cache = str(tmp_path / "cache")
        os.makedirs(cache)
        live = os.path.join(cache, ".staging-cohort-otherlive-full")
        os.makedirs(live)
        with open(
            os.path.join(cache, ".lock-cohort-otherlive-full"), "w"
        ) as f:
            f.write(str(os.getpid()))  # that cohort's populate is LIVE
        dead = os.path.join(cache, ".staging-cohort-otherdead-full")
        os.makedirs(dead)
        with open(
            os.path.join(cache, ".lock-cohort-otherdead-full"), "w"
        ) as f:
            f.write("999999999")  # owner is gone
        src = HttpVariantSource(url, cache_dir=cache, cold_stream=False)
        assert [c.id for c in src.list_callsets(VSID)]
        assert os.path.isdir(live)  # live peer untouched
        assert not os.path.exists(dead)  # dead run's staging reaped
        os.unlink(os.path.join(cache, ".lock-cohort-otherlive-full"))

    def test_prune_spares_peer_mid_acquisition_before_pid_lands(
        self, served, tmp_path
    ):
        """The in-acquisition window: a peer has opened + flocked its
        lock file but not yet written its pid (the file is EMPTY — or
        still holds a dead run's stale pid). The prune loop must probe
        with flock, not trust the file content: classifying that lock
        as stale would unlink it and rmtree the peer's staging while
        the peer legitimately holds the flock, letting a third
        populator sweep the peer's in-flight files (the TOCTOU the
        shared-staging lock exists to prevent)."""
        import fcntl

        root, url = served
        cache = str(tmp_path / "cache")
        os.makedirs(cache)
        staging = os.path.join(cache, ".staging-cohort-acquiring-full")
        os.makedirs(staging)
        lock = os.path.join(cache, ".lock-cohort-acquiring-full")
        fd = os.open(lock, os.O_CREAT | os.O_RDWR)
        fcntl.flock(fd, fcntl.LOCK_EX | fcntl.LOCK_NB)
        try:  # pid NOT yet written: content is empty, flock is held
            src = HttpVariantSource(
                url, cache_dir=cache, cold_stream=False
            )
            assert [c.id for c in src.list_callsets(VSID)]
            assert os.path.isdir(staging)  # spared: flock says LIVE
            assert os.path.exists(lock)
        finally:
            os.close(fd)
        os.unlink(lock)

    def test_dead_lock_holder_is_broken_and_staging_reused(
        self, served, tmp_path
    ):
        root, url = served
        cache = str(tmp_path / "cache")
        os.makedirs(cache)
        ident = JsonlSource(root).cohort_identity()
        lock = os.path.join(cache, f".lock-cohort-{ident}-full")
        with open(lock, "w") as f:
            f.write("999999999")  # a pid that cannot be alive
        src = HttpVariantSource(url, cache_dir=cache, cold_stream=False)
        assert [c.id for c in src.list_callsets(VSID)]
        assert not os.path.exists(lock)  # broken, then released

    def test_foreign_host_owner_is_never_judged_dead(
        self, served, tmp_path
    ):
        """On a shared cache mount the lock records ``pid@host``, and a
        FOREIGN host's owner must always count as alive: os.kill probes
        only the local pid table, so a remote peer's pid number is
        meaningless here — judging it 'dead' would reap a live remote
        populate's staging (the exact mount-without-flock-propagation
        case the recorded owner exists for). A dead-LOOKING foreign
        owner therefore routes this populate to the isolated one-shot
        path and spares the foreign staging from the prune loop."""
        root, url = served
        cache = str(tmp_path / "cache")
        os.makedirs(cache)
        ident = JsonlSource(root).cohort_identity()
        # Same identity: a foreign host is mid-populate on the shared
        # staging. Its pid would read as dead in OUR pid table.
        lock = os.path.join(cache, f".lock-cohort-{ident}-full")
        with open(lock, "w") as f:
            f.write("999999999@some.other.host")
        shared = os.path.join(cache, f".staging-cohort-{ident}-full")
        os.makedirs(shared)
        probe = os.path.join(shared, "foreign-in-flight")
        with open(probe, "w") as f:
            f.write("remote peer bytes")
        # A different identity's foreign staging, also dead-by-pid.
        other = os.path.join(cache, ".staging-cohort-otherhost-full")
        os.makedirs(other)
        with open(
            os.path.join(cache, ".lock-cohort-otherhost-full"), "w"
        ) as f:
            f.write("999999999@some.other.host")
        src = HttpVariantSource(url, cache_dir=cache, cold_stream=False)
        got = [c.id for c in src.list_callsets(VSID)]
        want = [c.id for c in JsonlSource(root).list_callsets(VSID)]
        assert got == want  # populated via the isolated one-shot path
        assert os.path.exists(
            os.path.join(_mirror_root(cache), MIRROR_COMPLETE_MARKER)
        )
        # Neither foreign staging (nor lock) was touched.
        assert os.path.exists(probe)
        assert os.path.exists(lock)
        assert os.path.isdir(other)
        os.unlink(lock)
        os.unlink(os.path.join(cache, ".lock-cohort-otherhost-full"))

    def test_failed_upgrade_leaves_no_partials_in_mirror_root(
        self, served, tmp_path
    ):
        """A torn commit during a light→full upgrade must not leak
        ``.partial-*`` / ``*.tmp-*`` files into the COMPLETED mirror
        root: unlike staging dirs, the trusted root is never swept, so
        a leftover would accumulate forever (one per crashed upgrade)."""
        root, url = served
        cache = str(tmp_path / "cache")
        light = HttpVariantSource(
            url, cache_dir=cache, mirror_mode="light", cold_stream=False
        )
        assert [c.id for c in light.list_callsets(VSID)]
        mirror_root = _mirror_root(cache)
        assert mirror_root is not None
        plan = FaultPlan(
            seed=1,
            rules=[
                FaultRule(
                    site="mirror.write",
                    kind="torn",
                    match="variants.jsonl",
                    times=1,
                )
            ],
        )
        full = HttpVariantSource(
            url, cache_dir=cache, mirror_mode="full", cold_stream=False
        )
        shard = shards_for_references(REFS, 20_000)[0]
        with faults.active_plan(plan):
            with pytest.raises(IOError):
                list(full.stream_variants(VSID, shard))
        assert plan.fired_total == 1
        leftovers = [
            e
            for e in os.listdir(mirror_root)
            if e.startswith(".partial-") or ".tmp-" in e
        ]
        assert leftovers == []
        # And the upgrade gate re-fires: a fresh full-mode consumer
        # completes the upgrade and serves records with parity.
        full2 = HttpVariantSource(
            url, cache_dir=cache, mirror_mode="full", cold_stream=False
        )
        got = list(full2.stream_variants(VSID, shard))
        want = list(JsonlSource(root).stream_variants(VSID, shard))
        assert got == want
        assert os.path.exists(
            os.path.join(mirror_root, "variants.jsonl")
        )

    def test_tolerated_sidecar_failure_publishes_no_tmp(
        self, served, tmp_path
    ):
        """In full mode a failed sidecar export is tolerated (the
        mirror parses locally) — but the tolerated failure still
        publishes the staging as the COMPLETED mirror root, so the
        cleanup must also remove the sidecar's *.tmp-* partial or a
        sidecar-sized leftover leaks into the trusted root forever."""
        root, url = served
        cache = str(tmp_path / "cache")
        plan = FaultPlan(
            seed=1,
            rules=[
                FaultRule(
                    site="mirror.write",
                    kind="torn",
                    match=SIDECAR_BASENAME,
                    times=1,
                )
            ],
        )
        src = HttpVariantSource(url, cache_dir=cache, cold_stream=False)
        with faults.active_plan(plan):
            got = [c.id for c in src.list_callsets(VSID)]
        assert plan.fired_total == 1
        want = [c.id for c in JsonlSource(root).list_callsets(VSID)]
        assert got == want
        mirror_root = _mirror_root(cache)
        assert mirror_root is not None
        assert os.path.exists(
            os.path.join(mirror_root, MIRROR_COMPLETE_MARKER)
        )
        assert not os.path.exists(
            os.path.join(mirror_root, SIDECAR_BASENAME)
        )
        leftovers = [
            e for e in os.listdir(mirror_root) if ".tmp-" in e
        ]
        assert leftovers == []

    def test_probe_resolve_failure_defers_to_ingest_seam(self):
        """A transient failure inside cold_stream_active's resolve (the
        /identity round-trip, or a synchronous light→full upgrade) must
        answer 'not cold-streaming' — not kill the run from the driver
        thread. The resolve then happens lazily at the first shard
        fetch, inside the per-shard --shard-retries seam that has
        always covered it."""
        import threading

        from spark_examples_tpu.genomics.mirror import (
            refresh_cold_stream,
        )

        class _FlakySource:
            _cold_stream = True
            _mirror = None
            _mirror_lock = threading.Lock()

            def _resolve_mirror(self):
                raise IOError("transient: identity fetch failed")

        assert refresh_cold_stream(_FlakySource()) is False

    @pytest.mark.parametrize(
        "flip", ["file_middle", "first_chunk", "middle_chunk", "last_chunk"]
    )
    def test_corrupt_sidecar_member_falls_back_and_rebuilds(
        self, cohort_dir, tmp_path, monkeypatch, flip
    ):
        """mmap fast path keeps np.load's corruption detection: a
        bit-flipped committed sidecar must fail its CRC and trigger the
        rebuild, never serve garbage ordinals. The ``*_chunk`` cases
        check the ``ords`` member in four chunks, on threads, and flip
        a byte inside one of them."""
        import shutil as _shutil
        import struct
        import zipfile

        from spark_examples_tpu import obs
        from spark_examples_tpu.genomics import sources as S

        root = str(tmp_path / "cohort")
        _shutil.copytree(cohort_dir, root)
        side = os.path.join(root, SIDECAR_BASENAME)
        want = [
            c.id for c in JsonlSource(cohort_dir).list_callsets(VSID)
        ]
        blob = bytearray(open(side, "rb").read())
        with zipfile.ZipFile(side) as zf:
            infos = zf.infolist()
        if flip == "file_middle":
            at = len(blob) // 2  # flip a payload bit
        else:
            (ords,) = [i for i in infos if i.filename == "ords.npy"]
            chunk = ords.file_size // 4
            monkeypatch.setattr(S, "_CRC_CHUNK_BYTES", chunk)
            monkeypatch.setattr(S, "_crc_threads", lambda: 8)
            multi = sum(i.file_size for i in infos
                        if i.file_size >= 2 * chunk)
            assert multi >= ords.file_size
            counters = obs.get_registry().snapshot()["counters"]
            key = 'ingest_sidecar_crc_bytes_total{mode="parallel"}'
            before = counters.get(key, 0.0)
            assert S._load_sidecar_mmap(side) is not None  # intact
            after = obs.get_registry().snapshot()["counters"][key]
            assert after - before == multi
            ho = ords.header_offset
            nlen, elen = struct.unpack("<HH", blob[ho + 26 : ho + 30])
            start = ho + 30 + nlen + elen
            index = {"first_chunk": 0, "middle_chunk": 2,
                     "last_chunk": 3}[flip]
            # The last chunk runs to the member's end.
            at = start + index * chunk + chunk // 2
        blob[at] ^= 0xFF
        with open(side, "wb") as f:
            f.write(blob)
        assert S._load_sidecar_mmap(side) is None  # CRC catches it
        src = JsonlSource(root)
        indexes = {c.id: i for i, c in enumerate(src.list_callsets(VSID))}
        shard = shards_for_references(REFS, 100_000)[0]
        got = src.stream_carrying_csr(VSID, shard, indexes)
        ref = JsonlSource(cohort_dir).stream_carrying_csr(
            VSID, shard, indexes
        )
        np.testing.assert_array_equal(ref[0], got[0])  # rebuilt, correct
        np.testing.assert_array_equal(ref[1], got[1])
        assert [c.id for c in src.list_callsets(VSID)] == want

    def test_stale_staging_for_other_identity_discarded(
        self, served, tmp_path
    ):
        root, url = served
        cache = str(tmp_path / "cache")
        ident = JsonlSource(root).cohort_identity()
        staging = os.path.join(cache, f".staging-cohort-{ident}-full")
        os.makedirs(staging)
        # A stale staging pinned to ANOTHER identity holding a poisoned
        # file that must never be donated to the new mirror.
        with open(os.path.join(staging, ".identity"), "w") as f:
            f.write("some-older-cohort")
        with open(os.path.join(staging, "callsets.json"), "w") as f:
            f.write("[]")  # poison: would break list_callsets if reused
        src = HttpVariantSource(url, cache_dir=cache, cold_stream=False)
        got = [c.id for c in src.list_callsets(VSID)]
        want = [c.id for c in JsonlSource(root).list_callsets(VSID)]
        assert got == want  # the poisoned file was discarded, not reused


class TestColdStreamTier:
    def test_cold_stream_serves_wire_and_writes_through(
        self, served, tmp_path
    ):
        root, url = served
        cache = str(tmp_path / "cache")
        src = HttpVariantSource(url, cache_dir=cache)  # cold_stream on
        assert src.cold_stream_active() is True
        local = JsonlSource(root)
        indexes = {
            c.id: i for i, c in enumerate(local.list_callsets(VSID))
        }
        # Shard CSR pairs ride the wire immediately, with parity.
        checked = 0
        for shard in shards_for_references(REFS, 15_000):
            want = local.stream_carrying_csr(VSID, shard, indexes)
            got = src.stream_carrying_csr(VSID, shard, indexes)
            if want is None:
                assert got is None
                continue
            np.testing.assert_array_equal(want[0], got[0])
            np.testing.assert_array_equal(want[1], got[1])
            checked += 1
        assert checked > 0
        # The write-through mirror completes as a SIDE EFFECT.
        mirror = src._resolve_mirror()
        assert isinstance(mirror, ColdStreamMirror) and not mirror
        assert mirror.join(timeout=60)
        mirror_root = _mirror_root(cache)
        assert mirror_root is not None
        assert os.path.exists(
            os.path.join(mirror_root, MIRROR_COMPLETE_MARKER)
        )
        # The next run is WARM: same cache, mirror served locally.
        warm = HttpVariantSource(url, cache_dir=cache)
        assert warm.cold_stream_active() is False
        shard = shards_for_references(REFS, 15_000)[0]
        want = local.stream_carrying_csr(VSID, shard, indexes)
        got = warm.stream_carrying_csr(VSID, shard, indexes)
        np.testing.assert_array_equal(want[0], got[0])
        np.testing.assert_array_equal(want[1], got[1])

    def test_resident_source_upgrades_to_mirror_at_run_boundary(
        self, served, tmp_path
    ):
        """A LONG-LIVED source (the serving engine runs every job
        against one resident instance) must not stay pinned to the
        wire tier forever after one cold resolve: once the write-
        through download has finished, the next run's
        ``cold_stream_active`` consultation drops the cached sentinel
        and re-resolves — reading the completed mirror from disk, with
        parity."""
        root, url = served
        cache = str(tmp_path / "cache")
        src = HttpVariantSource(url, cache_dir=cache)  # cold_stream on
        assert src.cold_stream_active() is True  # run 1: cold, wire
        mirror = src._resolve_mirror()
        assert isinstance(mirror, ColdStreamMirror)
        assert mirror.join(timeout=60)  # write-through lands
        # Run 2 on the SAME instance: the boundary consultation flips.
        assert src.cold_stream_active() is False
        upgraded = src._resolve_mirror()
        assert isinstance(upgraded, JsonlSource)
        local = JsonlSource(root)
        indexes = {
            c.id: i for i, c in enumerate(local.list_callsets(VSID))
        }
        shard = shards_for_references(REFS, 15_000)[0]
        want = local.stream_carrying_csr(VSID, shard, indexes)
        got = src.stream_carrying_csr(VSID, shard, indexes)
        np.testing.assert_array_equal(want[0], got[0])
        np.testing.assert_array_equal(want[1], got[1])

    def test_no_cold_stream_is_phased(self, served, tmp_path):
        root, url = served
        cache = str(tmp_path / "cache")
        src = HttpVariantSource(url, cache_dir=cache, cold_stream=False)
        # With the flag off this is a PURE probe: the phased mirror
        # download must not run here (the driver consults this before
        # ingest, and an eager download would sit OUTSIDE the per-shard
        # retry seam that has always covered the phased path's lazy
        # first-fetch resolve).
        assert src.cold_stream_active() is False
        assert not os.path.isdir(cache) or _mirror_root(cache) is None
        # First data access downloads the whole mirror before serving.
        assert [c.id for c in src.list_callsets(VSID)]
        mirror_root = _mirror_root(cache)
        assert mirror_root is not None
        assert os.path.exists(
            os.path.join(mirror_root, MIRROR_COMPLETE_MARKER)
        )

    def test_cold_stream_inactive_without_cache_dir(self, served):
        _, url = served
        assert HttpVariantSource(url).cold_stream_active() is False

    def test_g_bit_identical_cold_vs_phased_across_workers(
        self, served, tmp_path
    ):
        """The acceptance bit-identity pin: G from the cold-stream path
        equals the phased path's and the local sidecar's, bit for bit,
        at any worker count and either shard arrival order (cold-stream
        defaults to completion order; integer-exact accumulation makes
        arrival order irrelevant — same argument PR 3 pinned)."""
        root, url = served
        g_local = _gramian(JsonlSource(root))
        g_phased = _gramian(
            HttpVariantSource(
                url,
                cache_dir=str(tmp_path / "phased"),
                cold_stream=False,
            )
        )
        assert np.array_equal(g_local, g_phased)
        for workers in (1, 3):
            for order in ("manifest", "completion"):
                cache = str(
                    tmp_path / f"cold-{workers}-{order}"
                )
                g_cold = _gramian(
                    HttpVariantSource(url, cache_dir=cache),
                    ingest_workers=workers,
                    ingest_order=order,
                )
                assert np.array_equal(g_local, g_cold), (
                    workers,
                    order,
                )

    def test_cold_stream_telemetry_schema_valid(self, served, tmp_path):
        from spark_examples_tpu.obs.session import TelemetrySession

        root, url = served
        trace = str(tmp_path / "run.trace.json")
        metrics = str(tmp_path / "run.metrics.prom")
        with TelemetrySession(
            trace_out=trace, metrics_out=metrics
        ) as session:
            g = _gramian(
                HttpVariantSource(
                    url, cache_dir=str(tmp_path / "cache")
                )
            )
            assert g.shape[0] == 50
            snap = session.registry.snapshot()
        counters = snap["counters"]
        n_shards = len(shards_for_references(REFS, 15_000))
        fetched = sum(
            v
            for k, v in counters.items()
            if k.startswith("cold_stream_shards_total")
            and 'stage="fetched"' in k
        )
        accumulated = sum(
            v
            for k, v in counters.items()
            if k.startswith("cold_stream_shards_total")
            and 'stage="accumulated"' in k
        )
        assert fetched == accumulated == n_shards
        validate = _load_validate_trace()
        assert validate.validate_trace(trace) == []
        assert validate.validate_metrics(metrics) == []
        # The new spans really are on the timeline.
        events = json.load(open(trace))["traceEvents"]
        names = {e.get("name") for e in events}
        assert "ingest.fetch" in names
        assert "ingest.stream" in names

    def test_validate_metrics_rejects_unlabeled_cold_counter(
        self, tmp_path
    ):
        path = tmp_path / "bad.prom"
        path.write_text(
            "# HELP cold_stream_shards_total x\n"
            "# TYPE cold_stream_shards_total counter\n"
            "cold_stream_shards_total 3\n"
        )
        validate = _load_validate_trace()
        errs = validate.validate_metrics(str(path))
        assert errs and "stage" in errs[0]

    def test_validate_trace_rejects_unknown_ingest_span(self, tmp_path):
        path = tmp_path / "bad.trace.json"
        path.write_text(
            json.dumps(
                {
                    "traceEvents": [
                        {
                            "ph": "X",
                            "name": "ingest.fetchh",
                            "pid": 1,
                            "ts": 0,
                            "dur": 1,
                        }
                    ]
                }
            )
        )
        validate = _load_validate_trace()
        errs = validate.validate_trace(str(path))
        assert errs and "ingest.fetchh" in errs[0]


class TestIngestStreamSeam:
    """Satellite: the deterministic ``ingest.stream`` fault seam rides
    the per-shard RetryPolicy loop — chaos runs pin fault-free-identical
    results, and with retries off the failure is LOUD (GL005: no silent
    degradation, no ad-hoc sleeps)."""

    @pytest.mark.parametrize("kind", ["error", "stall", "truncate"])
    def test_fault_retries_to_identical_g(self, cohort_dir, kind):
        g_ref = _gramian(JsonlSource(cohort_dir))
        plan = FaultPlan(
            seed=7,
            rules=[FaultRule(site="ingest.stream", kind=kind, times=2)],
        )
        with faults.active_plan(plan):
            g = _gramian(JsonlSource(cohort_dir), shard_retries=3)
        assert plan.fired_total == 2
        assert np.array_equal(g_ref, g)

    @pytest.mark.parametrize("kind", ["error", "truncate"])
    def test_fault_without_retries_is_loud(self, cohort_dir, kind):
        plan = FaultPlan(
            seed=7,
            rules=[FaultRule(site="ingest.stream", kind=kind, times=1)],
        )
        with faults.active_plan(plan):
            with pytest.raises(IOError):
                _gramian(JsonlSource(cohort_dir), shard_retries=1)
        assert plan.fired_total == 1


class _SlowCohort:
    """Loopback cohort with simulated wire latency, so the acceptance
    measures PIPELINE STRUCTURE (parallel fetch + fetch/compute
    overlap), not loopback noise: a fixed RTT per shard frame request,
    throughput-shaped delays on the whole-file exports, and a cold
    sidecar response delay. Both paths pay the same per-byte prices —
    the streaming win comes from overlap, fewer bytes, and completion
    order, exactly the tentpole claim."""

    def __init__(self, inner):
        self._inner = inner
        self.frame_delay = 0.2
        self.line_delay = 0.1
        self.line_every = 20
        self.sidecar_delay = 0.5

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def stream_carrying_frame(self, *args, **kwargs):
        time.sleep(self.frame_delay)  # per-shard RTT
        return self._inner.stream_carrying_frame(*args, **kwargs)

    def export_lines(self, name):
        lines = self._inner.export_lines(name)

        def gen():
            for i, line in enumerate(lines):
                if i % self.line_every == 0:
                    time.sleep(self.line_delay)
                yield line

        return gen()

    def ensure_sidecar(self):
        time.sleep(self.sidecar_delay)
        return self._inner.ensure_sidecar()


class TestColdAcceptance:
    """The loopback cold acceptance (ISSUE 9): with an empty
    --cache-dir, streaming cold ingest beats the phased cold path by
    >= 2x wall time, the first gramian.accumulate span begins before
    the last ingest.fetch span ends, and G is bit-identical between the
    two paths."""

    def test_streaming_beats_phased_and_overlaps_device(
        self, cohort_dir, tmp_path
    ):
        from spark_examples_tpu.obs.session import TelemetrySession

        # Warm the accumulate executables on the run's exact shapes: the
        # acceptance measures INGEST structure, and a first-call XLA
        # compile inside the timed window would both skew the ratio and
        # push the first accumulate dispatch past the fetch tail.
        _gramian(JsonlSource(cohort_dir))
        server = GenomicsServiceServer(
            _SlowCohort(JsonlSource(cohort_dir))
        ).start()
        trace = str(tmp_path / "cold.trace.json")
        try:
            url = f"http://127.0.0.1:{server.port}"

            def timed(cold_stream, tag, trace_out=None):
                cache = str(tmp_path / f"cache-{tag}")
                shutil.rmtree(cache, ignore_errors=True)  # EMPTY cache
                src = HttpVariantSource(
                    url, cache_dir=cache, cold_stream=cold_stream
                )
                t0 = time.perf_counter()
                if trace_out is None:
                    g = _gramian(src, ingest_workers=4)
                else:
                    with TelemetrySession(trace_out=trace_out):
                        g = _gramian(src, ingest_workers=4)
                return time.perf_counter() - t0, g

            t_stream, g_stream = timed(True, "stream", trace_out=trace)
            t_phased, g_phased = timed(False, "phased")
            assert np.array_equal(g_stream, g_phased)
            ratio = t_phased / t_stream
            assert ratio >= 2.0, (
                f"streaming cold ingest only {ratio:.2f}x faster than "
                f"phased ({t_stream:.2f}s vs {t_phased:.2f}s)"
            )
            # Span-overlap criterion: the device accumulator started
            # while later shards were still inside their fetch spans.
            events = json.load(open(trace))["traceEvents"]
            fetch = [
                e
                for e in events
                if e.get("name") == "ingest.fetch" and e.get("ph") == "X"
            ]
            acc = [
                e
                for e in events
                if e.get("name") == "gramian.accumulate"
                and e.get("ph") == "X"
            ]
            assert fetch and acc
            first_acc = min(e["ts"] for e in acc)
            last_fetch_end = max(e["ts"] + e["dur"] for e in fetch)
            assert first_acc < last_fetch_end, (
                "first gramian.accumulate began only after the last "
                "ingest.fetch ended — no fetch/compute overlap"
            )
            validate = _load_validate_trace()
            assert validate.validate_trace(trace) == []
        finally:
            server.stop()
