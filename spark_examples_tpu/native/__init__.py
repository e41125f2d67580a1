"""ctypes loader for the native ingest core, with transparent fallback.

The library is built from the committed ``genomics_native.cpp`` into
``_genomics_native.<sha12 of the source>.so`` on first use (git-ignored);
``load()`` returns the compiled library handle or ``None``; callers keep a
pure-Python path so the framework runs on hosts without a toolchain (set
``SPARK_EXAMPLES_TPU_NO_NATIVE=1`` to force the fallback — used by tests to
cover both paths).
"""

from __future__ import annotations

import contextlib
import ctypes
import os
import subprocess
import threading
from typing import Optional

__all__ = ["load", "native_available", "force_fallback", "CohortCsr"]


@contextlib.contextmanager
def force_fallback():
    """Force the pure-Python/numpy fallback paths for the duration.

    Sets the ``SPARK_EXAMPLES_TPU_NO_NATIVE`` kill switch — which
    ``load()`` re-checks on every call, so the toggle works mid-process
    — and RESTORES any pre-existing value on exit (the CI fallback lane
    exports it run-wide; popping it would silently re-enable the native
    path for everything after the first caller). The one helper the
    tests and bench share, so the env contract can't drift between
    copies."""
    old = os.environ.get("SPARK_EXAMPLES_TPU_NO_NATIVE")
    os.environ["SPARK_EXAMPLES_TPU_NO_NATIVE"] = "1"
    try:
        yield
    finally:
        if old is None:
            os.environ.pop("SPARK_EXAMPLES_TPU_NO_NATIVE", None)
        else:
            os.environ["SPARK_EXAMPLES_TPU_NO_NATIVE"] = old


class CohortCsr(ctypes.Structure):
    """Mirror of the C CohortCsr result struct (genomics_native.cpp)."""

    _fields_ = [
        ("n_variants", ctypes.c_int64),
        ("n_calls", ctypes.c_int64),
        ("n_contigs", ctypes.c_int64),
        ("n_vsids", ctypes.c_int64),
        ("error", ctypes.c_int64),
        ("error_line", ctypes.c_int64),
        ("starts", ctypes.POINTER(ctypes.c_int64)),
        ("ends", ctypes.POINTER(ctypes.c_int64)),
        ("contig_code", ctypes.POINTER(ctypes.c_int32)),
        ("vsid_code", ctypes.POINTER(ctypes.c_int32)),
        ("afs", ctypes.POINTER(ctypes.c_double)),
        ("offsets", ctypes.POINTER(ctypes.c_int64)),
        ("ords", ctypes.POINTER(ctypes.c_int32)),
        ("contig_blob", ctypes.POINTER(ctypes.c_char)),
        ("contig_offs", ctypes.POINTER(ctypes.c_int64)),
        ("vsid_blob", ctypes.POINTER(ctypes.c_char)),
        ("vsid_offs", ctypes.POINTER(ctypes.c_int64)),
        ("ref_blob", ctypes.POINTER(ctypes.c_char)),
        ("ref_offs", ctypes.POINTER(ctypes.c_int64)),
        ("alt_blob", ctypes.POINTER(ctypes.c_char)),
        ("alt_offs", ctypes.POINTER(ctypes.c_int64)),
    ]

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, "genomics_native.cpp")


def _source_digest() -> str:
    import hashlib

    with open(_SRC, "rb") as f:
        return hashlib.sha256(f.read()).hexdigest()[:12]


# The library is keyed by the CONTENT of the committed source: a build
# from another revision (or a file copied in with a fresh mtime) can never
# be taken for this one, and a changed source simply builds a new file.
_SO = os.path.join(_HERE, f"_genomics_native.{_source_digest()}.so")

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_tried = False


def _build() -> bool:
    # Compile to a process-unique temp path and rename atomically:
    # concurrent builders (multi-host launch, pytest-xdist) must never
    # leave a half-written .so where another process dlopens it.
    tmp = f"{_SO}.{os.getpid()}.tmp"
    cmd = [
        "g++",
        "-O3",
        "-shared",
        "-fPIC",
        "-std=c++17",
        "-pthread",
        _SRC,
        "-o",
        tmp,
    ]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
        os.replace(tmp, _SO)
        return True
    except (OSError, subprocess.SubprocessError):
        try:
            os.unlink(tmp)
        except OSError:
            pass
        return False


def load() -> Optional[ctypes.CDLL]:
    global _lib, _tried
    if os.environ.get("SPARK_EXAMPLES_TPU_NO_NATIVE") == "1":
        return None
    if _tried:  # lock-free fast path once resolved (hot-loop callers)
        return _lib
    with _lock:
        if _tried:
            return _lib
        # Alternate-library override (scripts/sanitize_native.sh): point
        # the loader at a sanitizer-instrumented build WITHOUT touching
        # the canonical .so — overwriting it in place would leave an
        # ASan-instrumented library (which needs its runtime preloaded)
        # for the next uninstrumented run to dlopen and die on. Checked
        # BEFORE _tried is set: an override that cannot load must raise
        # on EVERY call — caching the failure would hand every later
        # caller a silent numpy fallback, the exact green-while-
        # covering-nothing mode the sanitizer gate exists to prevent.
        override = os.environ.get("SPARK_EXAMPLES_TPU_NATIVE_SO")
        if override:
            try:
                lib = _bind(ctypes.CDLL(override))
            except OSError as e:
                raise OSError(
                    f"SPARK_EXAMPLES_TPU_NATIVE_SO={override!r} did not "
                    f"load: {e}"
                ) from e
            _lib = lib
            _tried = True
            return _lib
        _tried = True
        if not os.path.exists(_SO) and not _build():
            return None
        try:
            lib = ctypes.CDLL(_SO)
        except OSError:
            return None
        _lib = _bind(lib)
        return _lib


def _bind(lib: ctypes.CDLL) -> ctypes.CDLL:
    """Declare the ctypes signatures on a freshly-dlopened library."""
    lib.pack_calls.argtypes = [
        ctypes.c_void_p,
        ctypes.c_void_p,
        ctypes.c_int64,
        ctypes.c_int64,
        ctypes.c_int64,
        ctypes.c_void_p,
    ]
    lib.csr_to_packed_blocks.argtypes = [
        ctypes.c_void_p,
        ctypes.c_void_p,
        ctypes.c_int64,
        ctypes.c_int64,
        ctypes.c_int64,
        ctypes.c_void_p,
    ]
    lib.csr_to_packed_blocks.restype = ctypes.c_int64
    lib.gather_shard_csr.argtypes = [
        ctypes.c_void_p,
        ctypes.c_void_p,
        ctypes.c_int64,
        ctypes.c_void_p,
        ctypes.c_int64,
        ctypes.c_void_p,
        ctypes.c_int64,
        ctypes.c_void_p,
        ctypes.c_int64,
        ctypes.c_void_p,
        ctypes.c_int64,
    ]
    lib.gather_shard_csr.restype = ctypes.c_int64
    lib.murmur3_x64_128.argtypes = [
        ctypes.c_void_p,
        ctypes.c_int64,
        ctypes.c_uint64,
        ctypes.c_void_p,
    ]
    lib.murmur3_x64_128_batch.argtypes = [
        ctypes.c_void_p,
        ctypes.c_void_p,
        ctypes.c_int64,
        ctypes.c_uint64,
        ctypes.c_void_p,
    ]
    # The ctypes CohortCsr mirror above must match the struct the
    # library was built with.
    _ABI = 2
    lib.cohort_csr_abi_version.restype = ctypes.c_int64
    if lib.cohort_csr_abi_version() != _ABI:
        raise OSError(
            f"native CohortCsr ABI {lib.cohort_csr_abi_version()} != "
            f"the loader's mirror ({_ABI})"
        )
    lib.parse_cohort_jsonl.argtypes = [
        ctypes.c_char_p,
        ctypes.c_void_p,
        ctypes.c_void_p,
        ctypes.c_int64,
    ]
    lib.parse_cohort_jsonl.restype = ctypes.POINTER(CohortCsr)
    lib.cohort_csr_free.argtypes = [ctypes.POINTER(CohortCsr)]
    return lib


def native_available() -> bool:
    return load() is not None
