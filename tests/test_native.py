"""Native core: build, byte-parity with Python fallbacks, speed sanity."""

import numpy as np
import pytest

from spark_examples_tpu.native import load, native_available


pytestmark = pytest.mark.skipif(
    not native_available(), reason="native toolchain unavailable"
)


class TestMurmurParity:
    def test_byte_identical_to_python(self):
        from spark_examples_tpu.genomics.hashing import (
            _murmur3_py,
            murmur3_x64_128,
        )

        rng = np.random.default_rng(0)
        for n in list(range(0, 40)) + [1000, 4096]:
            data = bytes(rng.integers(0, 256, size=n, dtype=np.uint8))
            assert murmur3_x64_128(data) == _murmur3_py(data), n

    def test_batch_matches_single(self):
        import ctypes

        lib = load()
        strings = [b"", b"a", b"hello world", b"x" * 33]
        blob = b"".join(strings)
        offsets = np.zeros(len(strings) + 1, np.int64)
        for i, s in enumerate(strings):
            offsets[i + 1] = offsets[i] + len(s)
        out = ctypes.create_string_buffer(16 * len(strings))
        lib.murmur3_x64_128_batch(
            blob, offsets.ctypes.data, len(strings), 0, out
        )
        from spark_examples_tpu.genomics.hashing import _murmur3_py

        for i, s in enumerate(strings):
            assert out.raw[i * 16 : (i + 1) * 16] == _murmur3_py(s)


class TestPackCalls:
    def test_matches_python_fallback(self, monkeypatch):
        from spark_examples_tpu.arrays.blocks import densify_calls

        rng = np.random.default_rng(1)
        calls = [
            list(rng.choice(50, size=rng.integers(0, 50), replace=False))
            for _ in range(200)
        ]
        native = densify_calls(calls, 50, 256)

        monkeypatch.setenv("SPARK_EXAMPLES_TPU_NO_NATIVE", "1")
        fallback = densify_calls(calls, 50, 256)
        np.testing.assert_array_equal(native, fallback)

    def test_out_of_range_index_raises_both_paths(self, monkeypatch):
        from spark_examples_tpu.arrays.blocks import densify_calls

        with pytest.raises(ValueError, match="out of range"):
            densify_calls([[0, 99], [1]], 3, 2)
        with pytest.raises(ValueError, match="out of range"):
            densify_calls([[-1]], 3, 1)
        monkeypatch.setenv("SPARK_EXAMPLES_TPU_NO_NATIVE", "1")
        with pytest.raises(ValueError, match="out of range"):
            densify_calls([[0, 99], [1]], 3, 2)
        with pytest.raises(ValueError, match="out of range"):
            densify_calls([[-1]], 3, 1)


class TestNativeCohortParser:
    def _dump(self, tmp_path):
        from spark_examples_tpu.genomics.fixtures import synthetic_cohort

        root = str(tmp_path / "c")
        synthetic_cohort(
            10,
            120,
            seed=3,
            dropped_contig_every=9,
            reference_blocks_every=13,
            references="17:41196311:41277499,13:33628137:33728137",
        ).dump(root)
        return root

    def test_native_parse_matches_python(self, tmp_path):
        import json

        import numpy as np
        import pytest

        from spark_examples_tpu.genomics.sources import (
            JsonlSource,
            _CsrCohort,
        )
        from spark_examples_tpu.native import load

        if load() is None:
            pytest.skip("native core unavailable")
        root = self._dump(tmp_path)
        js = JsonlSource(root)
        with js._open("callsets.json") as f:
            ids = [r["id"] for r in json.load(f)]
        native = _CsrCohort._parse_native(root, ids)
        python = _CsrCohort._parse_python(js._open, ids)
        assert native is not None
        for name, a, b in zip(
            (
                "contig_table",
                "rec_contig",
                "starts",
                "vsid_table",
                "rec_vsid",
                "afs",
                "offsets",
                "ords",
            ),
            native,
            python,
        ):
            if isinstance(a, list):
                assert a == b, name
            else:
                np.testing.assert_array_equal(a, b, err_msg=name)

    def test_anomaly_falls_back_to_python(self, tmp_path):
        """Any construct outside the interchange schema (here an escape in
        an extracted string) makes the native parser refuse the whole
        file; results still come out right via the Python parse."""
        import json
        import os

        import pytest

        from spark_examples_tpu.genomics.callsets import CallsetIndex
        from spark_examples_tpu.genomics.fixtures import (
            DEFAULT_VARIANT_SET_ID,
        )
        from spark_examples_tpu.genomics.shards import (
            shards_for_references,
        )
        from spark_examples_tpu.genomics.sources import (
            JsonlSource,
            _CsrCohort,
        )
        from spark_examples_tpu.native import load

        if load() is None:
            pytest.skip("native core unavailable")
        root = self._dump(tmp_path)
        # Append a record whose reference_name carries a JSON escape —
        # identical content either way, but outside the native subset.
        rec = {
            "reference_name": "chr_17",  # "chr_17" via escape? no —
            # ensure the RAW FILE contains a backslash escape:
            "start": 41200001,
            "end": 41200002,
            "variant_set_id": DEFAULT_VARIANT_SET_ID,
            "calls": [],
        }
        line = json.dumps(rec).replace("chr_17", "chr\\u005f17")
        with open(os.path.join(root, "variants.jsonl"), "a") as f:
            f.write(line + "\n")
        js = JsonlSource(root)
        with js._open("callsets.json") as f:
            ids = [r["id"] for r in json.load(f)]
        assert _CsrCohort._parse_native(root, ids) is None
        # Full path still serves (Python fallback builds the sidecar);
        # the escaped record is on a dropped contig either way.
        index = CallsetIndex.from_source(js, [DEFAULT_VARIANT_SET_ID])
        shard = shards_for_references("17:41196311:41277499", 100_000)[0]
        assert list(
            js.stream_carrying(DEFAULT_VARIANT_SET_ID, shard, index.indexes)
        )

    def test_gz_cohort_uses_python_parse(self, tmp_path):
        import gzip
        import os

        from spark_examples_tpu.genomics.callsets import CallsetIndex
        from spark_examples_tpu.genomics.fixtures import (
            DEFAULT_VARIANT_SET_ID,
        )
        from spark_examples_tpu.genomics.shards import (
            shards_for_references,
        )
        from spark_examples_tpu.genomics.sources import JsonlSource

        root = self._dump(tmp_path)
        plain = os.path.join(root, "variants.jsonl")
        with open(plain, "rb") as f_in, gzip.open(
            plain + ".gz", "wb"
        ) as f_out:
            f_out.write(f_in.read())
        os.unlink(plain)
        js = JsonlSource(root)
        index = CallsetIndex.from_source(js, [DEFAULT_VARIANT_SET_ID])
        shard = shards_for_references("17:41196311:41277499", 100_000)[0]
        assert list(
            js.stream_carrying(DEFAULT_VARIANT_SET_ID, shard, index.indexes)
        )

    def test_threaded_parse_matches_sequential(self, tmp_path, monkeypatch):
        """SPARK_EXAMPLES_TPU_PARSE_THREADS forces the range-split path
        even on tiny fixtures; output must be bit-identical to the
        sequential parse (same intern order, same CSR layout)."""
        import json

        import numpy as np
        import pytest

        from spark_examples_tpu.genomics.sources import (
            JsonlSource,
            _CsrCohort,
        )
        from spark_examples_tpu.native import load

        if load() is None or not hasattr(load(), "parse_cohort_jsonl"):
            pytest.skip("native core unavailable")
        root = self._dump(tmp_path)
        js = JsonlSource(root)
        with js._open("callsets.json") as f:
            ids = [r["id"] for r in json.load(f)]
        monkeypatch.setenv("SPARK_EXAMPLES_TPU_PARSE_THREADS", "1")
        seq = _CsrCohort._parse_native(root, ids)
        monkeypatch.setenv("SPARK_EXAMPLES_TPU_PARSE_THREADS", "5")
        par = _CsrCohort._parse_native(root, ids)
        assert seq is not None and par is not None
        for name, a, b in zip(
            (
                "contig_table",
                "rec_contig",
                "starts",
                "vsid_table",
                "rec_vsid",
                "afs",
                "offsets",
                "ords",
                "extra_ids",
                "ends",
                "refs",
                "alts",
            ),
            seq,
            par,
        ):
            if isinstance(a, list):
                assert a == b, name
            else:
                np.testing.assert_array_equal(a, b, err_msg=name)


def _gather_cohort(seed=0, nv=400, ns=64, unaligned=False):
    """A hand-built CSR sidecar: empty rows, two variant sets, NaN AFs.
    ``unaligned`` serves offsets and ords as views one byte off their
    element alignment, as an mmap'd npz member may be."""
    from spark_examples_tpu.genomics.sources import _CsrCohort

    rng = np.random.default_rng(seed)
    lens = rng.integers(0, 12, nv)
    lens[rng.random(nv) < 0.25] = 0
    offsets = np.zeros(nv + 1, np.int64)
    np.cumsum(lens, out=offsets[1:])
    ords = rng.integers(0, ns, int(offsets[-1])).astype(np.int32)
    afs = rng.random(nv)
    afs[rng.random(nv) < 0.1] = np.nan
    if unaligned:
        def shifted(a):
            buf = np.zeros(a.nbytes + 1, np.uint8)
            view = np.frombuffer(buf.data, a.dtype, a.size, offset=1)
            view[:] = a
            return view

        offsets, ords = shifted(offsets), shifted(ords)
        assert not ords.flags.aligned and not offsets.flags.aligned
    data = {
        "contigs": np.array(["20"]),
        "seg_lo": np.array([0], np.int64),
        "seg_hi": np.array([nv], np.int64),
        "starts": np.arange(nv, dtype=np.int64) * 10,
        "vcode": rng.integers(0, 2, nv).astype(np.int32),
        "afs": afs,
        "offsets": offsets,
        "ords": ords,
        "vsids": np.array(["vs0", "vs1"]),
        "callset_ids": np.array([f"s{i}" for i in range(ns)]),
    }
    return _CsrCohort(data), {f"s{i}": i for i in range(ns)}


def _gather_both(monkeypatch, cohort, shard, indexes, vsid="", min_af=None,
                 errors=(KeyError, IndexError)):
    """carrying_csr on the native path, then on the numpy fallback."""
    from spark_examples_tpu import native
    from spark_examples_tpu.utils.stats import IoStats

    got = []
    for lib in (native.load(), None):
        with monkeypatch.context() as m:
            m.setattr(native, "load", lambda lib=lib: lib)
            try:
                got.append(
                    cohort.carrying_csr(
                        shard, indexes, vsid, IoStats(), min_af
                    )
                )
            except errors as e:
                got.append(e)
    return got


class TestShardGather:
    """``gather_shard_csr``: the sidecar shard's ragged gather and
    ordinal lookup in one native pass, byte-identical to the numpy
    path it replaces in ``_CsrCohort.carrying_csr``."""

    @pytest.mark.parametrize(
        "case",
        [
            "whole_contig",
            "sub_window",
            "vsid_filter",
            "min_af_filter",
            "vsid_and_min_af",
            "one_row",
            "empty_window",
            "all_rows_empty",
            "restricted_indexes",
            "unaligned_views",
        ],
    )
    @pytest.mark.parametrize("seed", [0, 1, 2])
    def test_pair_matches_numpy(self, monkeypatch, case, seed):
        from spark_examples_tpu.genomics.shards import Shard

        cohort, indexes = _gather_cohort(
            seed, unaligned=case == "unaligned_views"
        )
        d = cohort._d
        nv = d["starts"].size
        shard, vsid, min_af = Shard("chr20", 0, nv * 10), "", None
        if case == "sub_window":
            shard = Shard("20", 1005, 2995)
        elif case == "vsid_filter":
            vsid = "vs1"
        elif case == "min_af_filter":
            min_af = 0.4
        elif case == "vsid_and_min_af":
            vsid, min_af = "vs0", 0.2
        elif case == "one_row":
            row = int(np.flatnonzero(np.diff(d["offsets"]))[seed])
            shard = Shard("20", row * 10, row * 10 + 1)
        elif case == "empty_window":
            shard = Shard("21", 0, nv * 10)
        elif case == "all_rows_empty":
            empty = np.flatnonzero(np.diff(d["offsets"]) == 0)
            run = empty[np.flatnonzero(np.diff(empty) == 1)[0]]
            shard = Shard("20", int(run) * 10, int(run) * 10 + 11)
        elif case == "restricted_indexes":
            lo, hi = d["offsets"][100], d["offsets"][200]
            used = np.unique(d["ords"][lo:hi])
            perm = np.random.default_rng(seed).permutation(used.size)
            indexes = {f"s{o}": int(p) for o, p in zip(used, perm)}
            shard = Shard("20", 1000, 2000)
        native, python = _gather_both(
            monkeypatch, cohort, shard, indexes, vsid, min_af
        )
        if case in ("empty_window", "all_rows_empty"):
            assert native is None and python is None
            return
        assert python is not None
        for n_arr, p_arr in zip(native, python):
            assert n_arr.dtype == p_arr.dtype == np.int64
            assert n_arr.tobytes() == p_arr.tobytes()

    def test_unknown_callset_raises_same_key_error(self, monkeypatch):
        from spark_examples_tpu.genomics.shards import Shard

        cohort, indexes = _gather_cohort(3)
        d = cohort._d
        first = int(d["ords"][d["offsets"][50]])
        del indexes[f"s{first}"]
        native, python = _gather_both(
            monkeypatch, cohort, Shard("20", 500, 4000), indexes
        )
        assert type(native) is type(python) is KeyError
        assert native.args == python.args

    def test_ordinal_past_callsets_raises_index_error(self, monkeypatch):
        from spark_examples_tpu.genomics.shards import Shard

        cohort, indexes = _gather_cohort(5)
        d = cohort._d
        d["ords"][d["offsets"][60]] = d["callset_ids"].size + 3
        native, python = _gather_both(
            monkeypatch, cohort, Shard("20", 500, 4000), indexes
        )
        assert type(native) is type(python) is IndexError
        assert str(native) == str(python)

    @pytest.mark.parametrize("fault", ["past_ords", "decreasing"])
    def test_corrupt_offsets_raise_without_overrun(self, monkeypatch, fault):
        """A sidecar may come from a mirror's server: offsets that do not
        describe ranges of its ordinals make the native pass raise before
        it reads or writes out of bounds. (The numpy path raises on
        ranges past the ordinals and drops a decreasing row.)"""
        from spark_examples_tpu.genomics.shards import Shard

        cohort, indexes = _gather_cohort(4)
        offsets = cohort._d["offsets"].copy()
        row = int(np.flatnonzero(np.diff(offsets))[10])
        if fault == "past_ords":
            offsets[row + 1 :] += cohort._d["ords"].size
        else:
            offsets[row + 1] = offsets[row] - 1
        cohort._d["offsets"] = offsets
        native, python = _gather_both(
            monkeypatch, cohort, Shard("20", 0, 4000), indexes,
            errors=(KeyError, IndexError, ValueError),
        )
        assert isinstance(native, ValueError)
        assert "corrupt sidecar" in str(native)
        if fault == "past_ords":
            assert isinstance(python, IndexError)

    def test_sidecar_mmap_views_match_numpy(self, monkeypatch, tmp_path):
        """Through a real on-disk sidecar, read in place from its mmap."""
        from spark_examples_tpu.genomics.fixtures import synthetic_cohort
        from spark_examples_tpu.genomics.shards import Shard
        from spark_examples_tpu.genomics.sources import JsonlSource

        root = str(tmp_path / "cohort")
        synthetic_cohort(12, 90, seed=3).dump(root)
        source = JsonlSource(root)
        indexes = {c.id: i for i, c in enumerate(source.list_callsets(""))}
        cohort = source._ensure_csr()
        native, python = _gather_both(
            monkeypatch, cohort, Shard("17", 41196311, 41277499), indexes
        )
        assert native[0].size > 0
        for n_arr, p_arr in zip(native, python):
            assert n_arr.tobytes() == p_arr.tobytes()


class TestNativeSoOverride:
    """SPARK_EXAMPLES_TPU_NATIVE_SO (the sanitizer-gate injection seam,
    scripts/sanitize_native.sh): a valid override loads and binds; an
    invalid one raises LOUDLY on EVERY load() call — caching the
    failure would hand later callers a silent numpy fallback, turning
    the sanitizer gate green while instrumenting nothing."""

    def test_override_points_at_canonical_so_and_binds(self):
        import subprocess
        import sys

        from spark_examples_tpu.native import _SO

        code = (
            "from spark_examples_tpu.native import load\n"
            "lib = load()\n"
            "assert lib is not None\n"
            "assert hasattr(lib, 'pack_calls')\n"
            "print('ok')\n"
        )
        proc = subprocess.run(
            [sys.executable, "-c", code],
            capture_output=True,
            text=True,
            env={
                **__import__("os").environ,
                "SPARK_EXAMPLES_TPU_NATIVE_SO": _SO,
            },
        )
        assert proc.returncode == 0, proc.stderr
        assert "ok" in proc.stdout

    def test_bad_override_raises_on_every_call(self):
        import subprocess
        import sys

        code = (
            "from spark_examples_tpu.native import load\n"
            "for attempt in range(2):\n"
            "    try:\n"
            "        load()\n"
            "    except OSError as e:\n"
            "        assert 'SPARK_EXAMPLES_TPU_NATIVE_SO' in str(e)\n"
            "    else:\n"
            "        raise SystemExit(f'silent fallback on attempt {attempt}')\n"
            "print('raised twice')\n"
        )
        proc = subprocess.run(
            [sys.executable, "-c", code],
            capture_output=True,
            text=True,
            env={
                **__import__("os").environ,
                "SPARK_EXAMPLES_TPU_NATIVE_SO": "/nonexistent/lib.so",
            },
        )
        assert proc.returncode == 0, proc.stderr + proc.stdout
        assert "raised twice" in proc.stdout


class TestContentKeyedBuild:
    def test_library_file_is_keyed_by_source_content(
        self, monkeypatch, tmp_path
    ):
        """The library loaded is the one built from THIS source: its file
        name carries the source's content hash (never an mtime check), so
        an edited source can never load a library built from another."""
        import hashlib
        import os

        from spark_examples_tpu import native

        with open(native._SRC, "rb") as f:
            src = f.read()
        digest = hashlib.sha256(src).hexdigest()[:12]
        assert os.path.basename(native._SO) == f"_genomics_native.{digest}.so"
        assert load() is not None and os.path.exists(native._SO)
        edited = tmp_path / "genomics_native.cpp"
        edited.write_bytes(src + b"\n// edited\n")
        monkeypatch.setattr(native, "_SRC", str(edited))
        assert native._source_digest() != digest
