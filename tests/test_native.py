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
