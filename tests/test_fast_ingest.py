"""Fused carrying-index ingest fast path ≡ the staged slow path.

The fast path (sources.stream_carrying / _carrying_records) exists because
per-call dataclass construction dominated host ingest at chr20 scale; its
contract is OBSERVABLE EQUALITY with stream_variants → af_filter →
carrying_sample_indices on every source type, stats included.
"""

import numpy as np
import pytest

from spark_examples_tpu.genomics.callsets import CallsetIndex
from spark_examples_tpu.genomics.datasets import (
    af_filter,
    carrying_sample_indices,
)
from spark_examples_tpu.genomics.fixtures import (
    DEFAULT_VARIANT_SET_ID,
    synthetic_cohort,
)
from spark_examples_tpu.genomics.shards import shards_for_references
from spark_examples_tpu.genomics.sources import FixtureSource, JsonlSource

REFS = "17:41196311:41277499"


def _slow(source, vsid, shards, indexes, min_af):
    out = []
    for shard in shards:
        stream = af_filter(source.stream_variants(vsid, shard), min_af)
        for v in stream:
            calls = carrying_sample_indices(v, indexes)
            if calls:
                out.append(calls)
    return out


def _fast(source, vsid, shards, indexes, min_af):
    out = []
    for shard in shards:
        out.extend(source.stream_carrying(vsid, shard, indexes, min_af))
    return out


def _cohort(**kw):
    return synthetic_cohort(
        12,
        80,
        seed=21,
        dropped_contig_every=9,
        reference_blocks_every=13,
        **kw,
    )


@pytest.mark.parametrize("min_af", [None, 0.2])
def test_fixture_source_parity(min_af):
    shards = shards_for_references(REFS, 20_000)
    slow_src, fast_src = _cohort(), _cohort()
    index = CallsetIndex.from_source(slow_src, [DEFAULT_VARIANT_SET_ID])
    slow = _slow(
        slow_src, DEFAULT_VARIANT_SET_ID, shards, index.indexes, min_af
    )
    fast = _fast(
        fast_src, DEFAULT_VARIANT_SET_ID, shards, index.indexes, min_af
    )
    assert fast == slow
    # Stats parity: same variants_read (counted post contig-drop,
    # pre AF-filter) and same request/partition accounting.
    assert fast_src.stats.variants_read == slow_src.stats.variants_read
    assert fast_src.stats.partitions == slow_src.stats.partitions


@pytest.mark.parametrize("min_af", [None, 0.2])
def test_jsonl_source_parity(tmp_path, min_af):
    _cohort().dump(str(tmp_path / "c"))
    shards = shards_for_references(REFS, 20_000)
    slow_src = JsonlSource(str(tmp_path / "c"))
    fast_src = JsonlSource(str(tmp_path / "c"))
    index = CallsetIndex.from_source(slow_src, [DEFAULT_VARIANT_SET_ID])
    assert _fast(
        fast_src, DEFAULT_VARIANT_SET_ID, shards, index.indexes, min_af
    ) == _slow(
        slow_src, DEFAULT_VARIANT_SET_ID, shards, index.indexes, min_af
    )
    assert fast_src.stats.variants_read == slow_src.stats.variants_read


@pytest.mark.parametrize("min_af", [None, 0.2])
def test_csr_direct_parity(tmp_path, min_af):
    """stream_carrying_csr + blocks_from_csr ≡ stream_carrying +
    blocks_from_calls — blocks bit-for-bit, stats identical. The CSR
    tier skips the array→list→array round-trip that was ~85% of warm
    host wall-clock at all-autosomes scale."""
    from spark_examples_tpu.arrays.blocks import (
        blocks_from_calls,
        blocks_from_csr,
    )

    _cohort().dump(str(tmp_path / "c"))
    shards = shards_for_references(REFS, 20_000)
    list_src = JsonlSource(str(tmp_path / "c"))
    csr_src = JsonlSource(str(tmp_path / "c"))
    index = CallsetIndex.from_source(list_src, [DEFAULT_VARIANT_SET_ID])

    lists = (
        calls
        for sh in shards
        for calls in list_src.stream_carrying(
            DEFAULT_VARIANT_SET_ID, sh, index.indexes, min_af
        )
    )
    want = list(blocks_from_calls(lists, index.size, 32))
    pairs = (
        csr_src.stream_carrying_csr(
            DEFAULT_VARIANT_SET_ID, sh, index.indexes, min_af
        )
        for sh in shards
    )
    got = list(blocks_from_csr(pairs, index.size, 32))
    assert len(got) == len(want)
    for a, b in zip(want, got):
        np.testing.assert_array_equal(a, b)
    assert csr_src.stats.variants_read == list_src.stats.variants_read
    assert csr_src.stats.partitions == list_src.stats.partitions


def test_csr_direct_unknown_callset_raises(tmp_path):
    """The CSR tier must fail on unknown callset ids exactly like the
    row tier (KeyError naming the id — VariantsPca.scala:59 analog)."""
    _cohort().dump(str(tmp_path / "c"))
    src = JsonlSource(str(tmp_path / "c"))
    index = CallsetIndex.from_source(src, [DEFAULT_VARIANT_SET_ID])
    shards = shards_for_references(REFS, 20_000)
    bad = {k: v for k, v in list(index.indexes.items())[:-1]}  # drop one
    with pytest.raises(KeyError):
        for sh in shards:
            src.stream_carrying_csr(DEFAULT_VARIANT_SET_ID, sh, bad)


@pytest.mark.parametrize("min_af", [None, 0.2])
def test_nonnumeric_af_behavior_identical_across_tiers(tmp_path, min_af):
    """A VCF "."-style AF must get the SAME treatment from the staged
    path, the fused record stream, and the CSR sidecar: missing → dropped
    under the filter, untouched without it (round-2 ADVICE: the sidecar
    dropped where the staged float() raised)."""
    import json

    cohort = _cohort()
    cohort.dump(str(tmp_path / "c"))
    cid = cohort.list_callsets(DEFAULT_VARIANT_SET_ID)[0].id
    bad = {
        "reference_name": "17",
        "start": 41_200_000,
        "end": 41_200_001,
        "reference_bases": "A",
        "variant_set_id": DEFAULT_VARIANT_SET_ID,
        "info": {"AF": ["."]},
        "calls": [{"callset_id": cid, "genotype": [1]}],
    }
    with open(tmp_path / "c" / "variants.jsonl", "a") as fh:
        fh.write(json.dumps(bad) + "\n")

    shards = shards_for_references(REFS, 20_000)
    slow_src = JsonlSource(str(tmp_path / "c"))
    fast_src = JsonlSource(str(tmp_path / "c"))
    index = CallsetIndex.from_source(slow_src, [DEFAULT_VARIANT_SET_ID])
    slow = _slow(
        slow_src, DEFAULT_VARIANT_SET_ID, shards, index.indexes, min_af
    )
    fast = _fast(
        fast_src, DEFAULT_VARIANT_SET_ID, shards, index.indexes, min_af
    )
    assert fast == slow
    # The record itself is served with the filter off, dropped with it on.
    clean = _slow(
        _cohort(), DEFAULT_VARIANT_SET_ID, shards, index.indexes, min_af
    )
    assert len(slow) == len(clean) + (0 if min_af else 1)


def test_http_source_parity():
    from spark_examples_tpu.genomics.service import (
        GenomicsServiceServer,
        HttpVariantSource,
    )

    server = GenomicsServiceServer(_cohort()).start()
    try:
        url = f"http://127.0.0.1:{server.port}"
        shards = shards_for_references(REFS, 20_000)
        slow_src = HttpVariantSource(url)
        fast_src = HttpVariantSource(url)
        index = CallsetIndex.from_source(
            slow_src, [DEFAULT_VARIANT_SET_ID]
        )
        assert _fast(
            fast_src, DEFAULT_VARIANT_SET_ID, shards, index.indexes, 0.2
        ) == _slow(
            slow_src, DEFAULT_VARIANT_SET_ID, shards, index.indexes, 0.2
        )
    finally:
        server.stop()


def test_variant_object_fallback_parity():
    # A fixture holding built Variant objects takes the order-preserving
    # fallback; results must still match the staged path.
    raw = _cohort()
    from spark_examples_tpu.genomics.sources import variant_from_record

    objs = [
        v
        for rec in raw._variants
        if (v := variant_from_record(rec)) is not None
    ]
    obj_src = FixtureSource(variants=objs, callsets=raw._callsets)
    ref_src = _cohort()
    shards = shards_for_references(REFS, 20_000)
    index = CallsetIndex.from_source(ref_src, [DEFAULT_VARIANT_SET_ID])
    assert _fast(
        obj_src, DEFAULT_VARIANT_SET_ID, shards, index.indexes, 0.2
    ) == _slow(
        ref_src, DEFAULT_VARIANT_SET_ID, shards, index.indexes, 0.2
    )


def test_unknown_callset_raises_keyerror():
    src = _cohort()
    shards = shards_for_references(REFS, 100_000)
    with pytest.raises(KeyError):
        for _ in src.stream_carrying(
            DEFAULT_VARIANT_SET_ID, shards[0], {"not-a-callset": 0}
        ):
            pass


def test_fault_injection_fires_in_fast_path():
    src = _cohort()
    shard = shards_for_references(REFS, 100_000)[0]
    src._fail_once.add(shard)
    with pytest.raises(IOError):
        list(src.stream_carrying(DEFAULT_VARIANT_SET_ID, shard, {}))
    assert src.stats.io_exceptions == 1


def test_driver_fused_equals_staged():
    from spark_examples_tpu.models.pca import VariantsPcaDriver
    from spark_examples_tpu.utils.config import PcaConfig

    class StagedOnly:
        """Proxy hiding stream_carrying so the driver takes the slow path."""

        def __init__(self, inner):
            self._inner = inner
            self.stats = inner.stats

        def list_callsets(self, vsid):
            return self._inner.list_callsets(vsid)

        def stream_variants(self, vsid, shard):
            return self._inner.stream_variants(vsid, shard)

    conf = PcaConfig(
        variant_set_ids=[DEFAULT_VARIANT_SET_ID],
        bases_per_partition=20_000,
        block_variants=32,
        min_allele_frequency=0.1,
    )
    fused_driver = VariantsPcaDriver(conf, _cohort())
    assert fused_driver._fused_ingest_possible()
    fused = fused_driver.run()
    staged_driver = VariantsPcaDriver(conf, StagedOnly(_cohort()))
    assert not staged_driver._fused_ingest_possible()
    staged = staged_driver.run()
    assert [r[0] for r in fused] == [r[0] for r in staged]
    np.testing.assert_allclose(
        np.array([r[1:] for r in fused]),
        np.array([r[1:] for r in staged]),
        atol=1e-6,
    )


class TestCsrSidecar:
    def test_sidecar_persists_and_serves_without_reparse(self, tmp_path):
        import os

        root = str(tmp_path / "c")
        _cohort().dump(root)
        shards = shards_for_references(REFS, 20_000)
        first = JsonlSource(root)
        index = CallsetIndex.from_source(first, [DEFAULT_VARIANT_SET_ID])
        want = _fast(first, DEFAULT_VARIANT_SET_ID, shards, index.indexes, None)
        sidecar = os.path.join(root, ".variants.csr.npz")
        assert os.path.exists(sidecar)

        # Corrupt the JSONL but keep its stat signature: a fresh source
        # must serve identical results purely from the sidecar — proof it
        # never re-parses.
        path = os.path.join(root, "variants.jsonl")
        st = os.stat(path)
        size = st.st_size
        with open(path, "r+b") as f:
            f.write(b"\x00" * min(64, size))
        os.utime(path, ns=(st.st_atime_ns, st.st_mtime_ns))
        assert os.stat(path).st_size == size
        fresh = JsonlSource(root)
        got = _fast(fresh, DEFAULT_VARIANT_SET_ID, shards, index.indexes, None)
        assert got == want

    def test_sidecar_invalidated_by_file_change(self, tmp_path):
        import json as _json
        import os

        root = str(tmp_path / "c")
        _cohort().dump(root)
        shards = shards_for_references(REFS, 100_000)
        first = JsonlSource(root)
        index = CallsetIndex.from_source(first, [DEFAULT_VARIANT_SET_ID])
        before = _fast(
            first, DEFAULT_VARIANT_SET_ID, shards, index.indexes, None
        )
        # Append one more carrying variant; mtime/size change → rebuild.
        rec = {
            "reference_name": "17",
            "start": 41200001,
            "end": 41200002,
            "reference_bases": "A",
            "alternate_bases": ["G"],
            "variant_set_id": DEFAULT_VARIANT_SET_ID,
            "calls": [
                {
                    "callset_id": f"{DEFAULT_VARIANT_SET_ID}-0",
                    "genotype": [0, 1],
                }
            ],
        }
        with open(os.path.join(root, "variants.jsonl"), "a") as f:
            f.write(_json.dumps(rec) + "\n")
        fresh = JsonlSource(root)
        after = _fast(
            fresh, DEFAULT_VARIANT_SET_ID, shards, index.indexes, None
        )
        assert len(after) == len(before) + 1


class TestVariantSetRule:
    """The ONE variant-set rule: falsy stored id = wildcard, non-empty
    must equal — identical across staged, fused, sidecar, and HTTP."""

    def _vsidless(self):
        src = _cohort()
        for rec in src._variants:
            rec.pop("variant_set_id", None)
        return src

    def test_http_round_trip_keeps_vsidless_records(self):
        # Serialization turns a missing key into an explicit "" — the
        # fused client path must keep those exactly like the staged one.
        from spark_examples_tpu.genomics.service import (
            GenomicsServiceServer,
            HttpVariantSource,
        )

        server = GenomicsServiceServer(self._vsidless()).start()
        try:
            url = f"http://127.0.0.1:{server.port}"
            shards = shards_for_references(REFS, 20_000)
            ref = CallsetIndex.from_source(
                _cohort(), [DEFAULT_VARIANT_SET_ID]
            )
            staged = _slow(
                HttpVariantSource(url),
                DEFAULT_VARIANT_SET_ID,
                shards,
                ref.indexes,
                None,
            )
            fused = _fast(
                HttpVariantSource(url),
                DEFAULT_VARIANT_SET_ID,
                shards,
                ref.indexes,
                None,
            )
            assert staged and fused == staged
        finally:
            server.stop()

    def test_jsonl_explicit_empty_vsid_is_wildcard(self, tmp_path):
        import json as _json
        import os

        root = str(tmp_path / "c")
        self._vsidless().dump(root)
        # dump writes records without the key; rewrite with explicit "".
        path = os.path.join(root, "variants.jsonl")
        recs = [
            {**_json.loads(line), "variant_set_id": ""}
            for line in open(path)
        ]
        with open(path, "w") as f:
            for rec in recs:
                f.write(_json.dumps(rec) + "\n")
        shards = shards_for_references(REFS, 20_000)
        ref = CallsetIndex.from_source(_cohort(), [DEFAULT_VARIANT_SET_ID])
        staged = _slow(
            JsonlSource(root), DEFAULT_VARIANT_SET_ID, shards, ref.indexes, None
        )
        fused = _fast(
            JsonlSource(root), DEFAULT_VARIANT_SET_ID, shards, ref.indexes, None
        )
        assert staged and fused == staged


class TestUnknownCallsetLazy:
    def test_out_of_scope_unknown_callset_does_not_crash_build(
        self, tmp_path
    ):
        """An unknown callset in a record OUTSIDE the query must not
        break fused ingest (the staged path never touches it); querying
        the bad record itself still raises with the true id."""
        import json as _json
        import os

        root = str(tmp_path / "c")
        _cohort().dump(root)
        bad = {
            "reference_name": "18",
            "start": 500,
            "end": 501,
            "reference_bases": "A",
            "alternate_bases": ["G"],
            "variant_set_id": DEFAULT_VARIANT_SET_ID,
            "calls": [{"callset_id": "ghost-callset", "genotype": [0, 1]}],
        }
        with open(os.path.join(root, "variants.jsonl"), "a") as f:
            f.write(_json.dumps(bad) + "\n")
        js = JsonlSource(root)
        index = CallsetIndex.from_source(js, [DEFAULT_VARIANT_SET_ID])
        shards = shards_for_references(REFS, 20_000)
        # chr17 query: works, ghost record never touched.
        assert _fast(js, DEFAULT_VARIANT_SET_ID, shards, index.indexes, None)
        # chr18 query hits the ghost record: KeyError with the true id.
        bad_shard = shards_for_references("18:0:1000", 1_000)[0]
        with pytest.raises(KeyError, match="ghost-callset"):
            list(
                js.stream_carrying(
                    DEFAULT_VARIANT_SET_ID, bad_shard, index.indexes
                )
            )


class TestFusedMultiDataset:
    """Keyed fused join/merge ≡ the staged multi-dataset path."""

    def _two_sets(self, src_factory=None):
        a = synthetic_cohort(8, 60, variant_set_id="setA", seed=1)
        b = synthetic_cohort(8, 60, variant_set_id="setB", seed=1)
        merged = FixtureSource(
            variants=a._variants + b._variants,
            callsets=a._callsets + b._callsets,
        )
        return merged

    def _three_sets(self):
        srcs = [
            synthetic_cohort(6, 40, variant_set_id=f"set{i}", seed=1)
            for i in range(3)
        ]
        return FixtureSource(
            variants=[r for s in srcs for r in s._variants],
            callsets=[c for s in srcs for c in s._callsets],
        )

    @pytest.mark.parametrize("min_af", [None, 0.2])
    def test_driver_join_fused_equals_staged(self, min_af):
        from spark_examples_tpu.models.pca import VariantsPcaDriver
        from spark_examples_tpu.utils.config import PcaConfig

        conf = PcaConfig(
            variant_set_ids=["setA", "setB"],
            bases_per_partition=20_000,
            block_variants=32,
            min_allele_frequency=min_af,
        )
        fused_driver = VariantsPcaDriver(conf, self._two_sets())
        assert fused_driver._fused_multi_possible()
        fused = fused_driver.run()
        staged_driver = VariantsPcaDriver(conf, self._two_sets())
        staged_calls = staged_driver.get_calls(
            [
                staged_driver.filter_dataset(d)
                for d in staged_driver.get_data()
            ]
        )
        g = staged_driver.get_similarity_matrix(staged_calls)
        staged = staged_driver.compute_pca(g)
        assert [r[0] for r in fused] == [r[0] for r in staged]
        np.testing.assert_allclose(
            np.array([r[1:] for r in fused]),
            np.array([r[1:] for r in staged]),
            atol=1e-6,
        )

    def test_three_set_merge_calls_identical(self):
        from spark_examples_tpu.models.pca import VariantsPcaDriver
        from spark_examples_tpu.utils.config import PcaConfig

        conf = PcaConfig(
            variant_set_ids=["set0", "set1", "set2"],
            bases_per_partition=20_000,
            block_variants=32,
        )
        fused_driver = VariantsPcaDriver(conf, self._three_sets())
        fused = sorted(map(tuple, fused_driver.get_calls_fused_multi()))
        staged_driver = VariantsPcaDriver(conf, self._three_sets())
        staged = sorted(
            map(
                tuple,
                staged_driver.get_calls(
                    [
                        staged_driver.filter_dataset(d)
                        for d in staged_driver.get_data()
                    ]
                ),
            )
        )
        assert fused and fused == staged

    def test_keyed_join_over_http(self, tmp_path):
        from spark_examples_tpu.genomics.service import (
            GenomicsServiceServer,
            HttpVariantSource,
        )
        from spark_examples_tpu.models.pca import VariantsPcaDriver
        from spark_examples_tpu.utils.config import PcaConfig

        server = GenomicsServiceServer(self._two_sets()).start()
        try:
            conf = PcaConfig(
                variant_set_ids=["setA", "setB"],
                bases_per_partition=20_000,
                block_variants=32,
            )
            remote = VariantsPcaDriver(
                conf, HttpVariantSource(f"http://127.0.0.1:{server.port}")
            )
            assert remote._fused_multi_possible()
            got = remote.run()
            local = VariantsPcaDriver(conf, self._two_sets()).run()
            np.testing.assert_allclose(
                np.array([r[1:] for r in got]),
                np.array([r[1:] for r in local]),
                atol=1e-6,
            )
        finally:
            server.stop()

    def test_keyed_join_over_jsonl(self, tmp_path):
        from spark_examples_tpu.models.pca import VariantsPcaDriver
        from spark_examples_tpu.utils.config import PcaConfig

        root = str(tmp_path / "c")
        self._two_sets().dump(root)
        conf = PcaConfig(
            variant_set_ids=["setA", "setB"],
            bases_per_partition=20_000,
            block_variants=32,
        )
        disk = VariantsPcaDriver(conf, JsonlSource(root)).run()
        mem = VariantsPcaDriver(conf, self._two_sets()).run()
        np.testing.assert_allclose(
            np.array([r[1:] for r in disk]),
            np.array([r[1:] for r in mem]),
            atol=1e-6,
        )

    def test_keyed_duplicate_identity_cross_product(self):
        from spark_examples_tpu.genomics.datasets import join_keyed

        def triple(contig, payload, calls):
            return (contig, payload, calls)

        a = [triple("17", b"p1", [0]), triple("17", b"p1", [1])]
        b = [triple("17", b"p1", [2]), triple("17", b"p2", [3])]
        out = sorted(join_keyed(iter(a), iter(b)))
        assert out == [[0, 2], [1, 2]]

    def test_keyed_empty_left_calls_still_join(self):
        # A record with NO carriers in set A still matches and
        # contributes B's carriers (reference joins records, not calls).
        from spark_examples_tpu.genomics.datasets import (
            calls_stream_keyed,
        )

        a = [("17", b"p1", [])]
        b = [("17", b"p1", [4, 5])]
        assert list(calls_stream_keyed([iter(a), iter(b)])) == [[4, 5]]


class TestSidecarRecovery:
    def test_corrupt_sidecar_rebuilds(self, tmp_path):
        import os

        root = str(tmp_path / "c")
        _cohort().dump(root)
        shards = shards_for_references(REFS, 20_000)
        index = CallsetIndex.from_source(
            JsonlSource(root), [DEFAULT_VARIANT_SET_ID]
        )
        want = _fast(
            JsonlSource(root), DEFAULT_VARIANT_SET_ID, shards, index.indexes, None
        )
        sidecar = os.path.join(root, ".variants.csr.npz")
        # Truncate to garbage: np.load raises BadZipFile, which must
        # trigger a rebuild, not a crash.
        with open(sidecar, "wb") as f:
            f.write(b"PK\x03\x04 not a real zip")
        got = _fast(
            JsonlSource(root), DEFAULT_VARIANT_SET_ID, shards, index.indexes, None
        )
        assert got == want

    def test_version_mismatch_rebuilds(self, tmp_path):
        import os

        import numpy as _np

        root = str(tmp_path / "c")
        _cohort().dump(root)
        shards = shards_for_references(REFS, 20_000)
        index = CallsetIndex.from_source(
            JsonlSource(root), [DEFAULT_VARIANT_SET_ID]
        )
        want = _fast(
            JsonlSource(root), DEFAULT_VARIANT_SET_ID, shards, index.indexes, None
        )
        sidecar = os.path.join(root, ".variants.csr.npz")
        # A structurally-valid npz from an older format version: the
        # digest embeds the version, so it must be rejected and rebuilt.
        with open(sidecar, "wb") as f:
            _np.savez(f, digest=_np.str_("v1|stale"))
        got = _fast(
            JsonlSource(root), DEFAULT_VARIANT_SET_ID, shards, index.indexes, None
        )
        assert got == want


class TestShardParallelIngest:
    """--ingest-workers: wall-clock parallelism with bit-identical
    results (round-2 verdict #2 — the shard-parallel cold ingest
    composition; perf is host-dependent, ORDER is not)."""

    def test_ordered_parallel_map_preserves_order(self):
        import time

        from spark_examples_tpu.utils.concurrency import (
            ordered_parallel_map,
        )

        def slow_square(x):
            time.sleep(0.002 * (7 - x % 8))  # later items finish earlier
            return x * x

        items = list(range(40))
        assert list(ordered_parallel_map(slow_square, items, 8)) == [
            x * x for x in items
        ]

    def test_ordered_parallel_map_error_position(self):
        from spark_examples_tpu.utils.concurrency import (
            ordered_parallel_map,
        )

        def boom(x):
            if x == 5:
                raise IOError("shard 5 failed")
            return x

        out = []
        with pytest.raises(IOError, match="shard 5"):
            for r in ordered_parallel_map(boom, range(10), 4):
                out.append(r)
        assert out == [0, 1, 2, 3, 4]  # everything before the failure

    @pytest.mark.parametrize("workers", [1, 3])
    def test_driver_results_bit_identical_across_worker_counts(
        self, tmp_path, workers
    ):
        from spark_examples_tpu.models.pca import VariantsPcaDriver
        from spark_examples_tpu.utils.config import PcaConfig

        _cohort().dump(str(tmp_path / "c"))

        def g_with(n_workers):
            conf = PcaConfig(
                variant_set_ids=[DEFAULT_VARIANT_SET_ID],
                bases_per_partition=20_000,
                block_variants=32,
                ingest_workers=n_workers,
            )
            driver = VariantsPcaDriver(
                conf, JsonlSource(str(tmp_path / "c"))
            )
            return np.asarray(
                driver.get_similarity_matrix(driver.get_calls_fused())
            )

        np.testing.assert_array_equal(g_with(workers), g_with(1))

    def test_multi_dataset_keyed_parallel_bit_identical(self, tmp_path):
        """The keyed path interleaves DIFFERENT variant sets from
        concurrent workers against one shared sidecar — the exact shape
        of the _allowed-mask race the review fixed; results must match
        serial exactly."""
        from spark_examples_tpu.models.pca import VariantsPcaDriver
        from spark_examples_tpu.utils.config import PcaConfig

        a = synthetic_cohort(8, 60, variant_set_id="setA", seed=1)
        b = synthetic_cohort(8, 60, variant_set_id="setB", seed=1)
        FixtureSource(
            variants=a._variants + b._variants,
            callsets=a._callsets + b._callsets,
        ).dump(str(tmp_path / "c"))

        def g_with(n_workers):
            conf = PcaConfig(
                variant_set_ids=["setA", "setB"],
                bases_per_partition=20_000,
                block_variants=32,
                ingest_workers=n_workers,
            )
            driver = VariantsPcaDriver(
                conf, JsonlSource(str(tmp_path / "c"))
            )
            assert driver._fused_multi_possible()
            return np.asarray(
                driver.get_similarity_matrix(
                    driver.get_calls_fused_multi()
                )
            )

        np.testing.assert_array_equal(g_with(4), g_with(1))

    def test_http_source_parallel_shards(self):
        """Concurrent in-flight shard requests against the threaded
        server — the reference's one-stream-per-task shape."""
        from spark_examples_tpu.genomics.service import (
            GenomicsServiceServer,
            HttpVariantSource,
        )
        from spark_examples_tpu.models.pca import VariantsPcaDriver
        from spark_examples_tpu.utils.config import PcaConfig

        server = GenomicsServiceServer(_cohort()).start()
        try:
            url = f"http://127.0.0.1:{server.port}"

            def result_with(n_workers):
                conf = PcaConfig(
                    variant_set_ids=[DEFAULT_VARIANT_SET_ID],
                    bases_per_partition=20_000,
                    block_variants=32,
                    ingest_workers=n_workers,
                )
                driver = VariantsPcaDriver(conf, HttpVariantSource(url))
                return np.asarray(
                    driver.get_similarity_matrix(driver.get_calls_fused())
                )

            np.testing.assert_array_equal(result_with(4), result_with(1))
        finally:
            server.stop()
