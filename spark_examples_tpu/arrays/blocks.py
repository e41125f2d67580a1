"""Densify per-variant sample-index lists into fixed-shape genotype blocks.

The bridge between the ragged host world (per-variant lists of carrying
sample indices, the ``RDD[Seq[Int]]`` interface at VariantsPca.scala:153-168)
and the static-shape device world: 0/1 indicator blocks
``X_blk ∈ {0,1}^(N × B)`` with a *fixed* block width B, so every
``G += X_blk @ X_blk.T`` step hits the same compiled executable.

Padding is free correctness-wise: a padded (all-zero) variant column
contributes nothing to the Gramian, so the final partial block is zero-padded
rather than specialising a second program shape.
"""

from __future__ import annotations

import time
from typing import Callable, Iterable, Iterator, List, Optional, Sequence, Tuple

import numpy as np

__all__ = [
    "densify_calls",
    "blocks_from_calls",
    "blocks_from_csr",
    "csr_windows",
    "packed_block_from_csr",
    "packed_blocks_from_csr",
    "restrict_window_to_sample_range",
    "round_up_multiple",
    "windows_from_calls",
    "DEFAULT_BLOCK_VARIANTS",
]


def round_up_multiple(n: int, multiple: int) -> int:
    """Smallest multiple of ``multiple`` ≥ n (tile/padding arithmetic)."""
    return -(-n // multiple) * multiple

# 2^13 variant columns per block: at N=2504 samples an int8 block is ~20 MB
# host-side — large enough to keep the MXU busy, small enough to double
# buffer in HBM comfortably. Multiple of 128 (lane width).
DEFAULT_BLOCK_VARIANTS = 8192


def densify_calls(
    calls: Sequence[Sequence[int]], n_samples: int, width: int = None
) -> np.ndarray:
    """Per-variant index lists → one (n_samples, width) 0/1 int8 block.

    Hot host loop of ingest; runs in the native core when built
    (:mod:`spark_examples_tpu.native`), with this numpy loop as fallback.
    """
    width = width if width is not None else len(calls)
    if width < len(calls):
        raise ValueError(
            f"width {width} < number of variants {len(calls)}"
        )
    from spark_examples_tpu.native import load

    lib = load()
    if lib is not None and calls:
        offsets = np.zeros(len(calls) + 1, dtype=np.int64)
        for i, c in enumerate(calls):
            offsets[i + 1] = offsets[i] + len(c)
        indices = np.fromiter(
            (s for c in calls for s in c), dtype=np.int64, count=offsets[-1]
        )
        _check_indices(indices, n_samples)
        x = np.zeros((n_samples, width), dtype=np.int8)
        lib.pack_calls(
            indices.ctypes.data,
            offsets.ctypes.data,
            len(calls),
            n_samples,
            width,
            x.ctypes.data,
        )
        return x
    x = np.zeros((n_samples, width), dtype=np.int8)
    for col, sample_indices in enumerate(calls):
        idx = np.asarray(sample_indices, dtype=np.int64)
        if idx.size:
            _check_indices(idx, n_samples)
            x[idx, col] = 1
    return x


def _check_indices(idx: np.ndarray, n_samples: int) -> None:
    """Out-of-range sample indices mean a corrupt callset index — fail
    loudly and identically on both the native and fallback paths (the
    reference throws on unknown callsets too, VariantsPca.scala:59)."""
    if idx.size and (idx.min() < 0 or idx.max() >= n_samples):
        bad = idx[(idx < 0) | (idx >= n_samples)][0]
        raise ValueError(
            f"sample index {bad} out of range for N={n_samples}"
        )


def blocks_from_calls(
    calls_iter: Iterable[Sequence[int]],
    n_samples: int,
    block_variants: int = DEFAULT_BLOCK_VARIANTS,
) -> Iterator[np.ndarray]:
    """Stream ragged call lists into fixed-shape zero-padded blocks."""
    buf: List[Sequence[int]] = []
    for calls in calls_iter:
        buf.append(calls)
        if len(buf) == block_variants:
            yield densify_calls(buf, n_samples, block_variants)
            buf = []
    if buf:
        yield densify_calls(buf, n_samples, block_variants)


def csr_windows(
    csr_iter,
    block_variants: int = DEFAULT_BLOCK_VARIANTS,
) -> Iterator[Tuple[np.ndarray, np.ndarray]]:
    """Regroup per-shard CSR pairs into per-BLOCK windows.

    The slicing stage of ingest (``ingest.slice`` on the obs timeline):
    consumes ``(indices, offsets)`` pairs in arrival order and yields one
    ``(indices, lens)`` window per ``block_variants`` variants (the tail
    window smaller), where ``lens[i]`` is variant i's carrier count and
    ``indices`` its carriers concatenated. Window composition depends
    only on the pair arrival order — never on who builds the block or
    when — which is what lets the build stage run on parallel workers
    with bit-identical output.

    ``csr_iter`` yields ``(indices, offsets)`` with ``offsets`` of length
    rows+1 (or None for empty shards, skipped).
    """
    import collections

    from spark_examples_tpu import obs

    # Pending (indices, lens) tails, head consumed via zero-copy views:
    # a pair spanning many blocks (one giant shard) is sliced with a
    # moving cursor, not re-concatenated per emitted window — the old
    # re-pack made slicing O(remainder) per block (quadratic over a big
    # pair), a serial cost no number of build workers can hide.
    pend: collections.deque = collections.deque()
    rows_buf = 0

    def emit(take: int):
        """Slice the first `take` buffered variants into one window."""
        nonlocal rows_buf
        with obs.span("ingest.slice", variants=take):
            idx_parts: List[np.ndarray] = []
            lens_parts: List[np.ndarray] = []
            need = take
            while need:
                idx, lens = pend[0]
                if lens.size <= need:
                    pend.popleft()
                    idx_parts.append(idx)
                    lens_parts.append(lens)
                    need -= lens.size
                else:
                    cut = int(lens[:need].sum())
                    idx_parts.append(idx[:cut])
                    lens_parts.append(lens[:need])
                    pend[0] = (idx[cut:], lens[need:])
                    need = 0
            rows_buf -= take
            if len(lens_parts) == 1:
                return idx_parts[0], lens_parts[0]
            return np.concatenate(idx_parts), np.concatenate(lens_parts)

    for pair in csr_iter:
        if pair is None:
            continue
        indices, offsets = pair
        if offsets.size <= 1:
            continue
        pend.append(
            (
                np.asarray(indices, dtype=np.int64),
                np.diff(np.asarray(offsets, dtype=np.int64)),
            )
        )
        rows_buf += offsets.size - 1
        while rows_buf >= block_variants:
            yield emit(block_variants)
    if rows_buf:
        yield emit(rows_buf)


def windows_from_calls(
    calls_iter: Iterable[Sequence[int]],
    block_variants: int = DEFAULT_BLOCK_VARIANTS,
) -> Iterator[Tuple[np.ndarray, np.ndarray]]:
    """Stream per-variant carrier lists into ``(indices, lens)`` windows.

    The call-list twin of :func:`csr_windows` for sources without a CSR
    tier (fixtures, staged multi-dataset merges): buffers
    ``block_variants`` variants and emits the same window shape the
    sparse Gramian engine consumes — per-variant carrier counts plus the
    concatenated carrier indices, NEVER a densified block. Window
    composition matches :func:`blocks_from_calls`'s block composition
    variant-for-variant, which is what makes the sparse and dense
    ingest routes directly comparable.
    """
    buf_idx: List[np.ndarray] = []
    buf_lens: List[int] = []

    def emit():
        lens = np.asarray(buf_lens, dtype=np.int64)
        idx = (
            np.concatenate(buf_idx)
            if buf_idx
            else np.zeros(0, dtype=np.int64)
        )
        return idx, lens

    for calls in calls_iter:
        arr = np.asarray(calls, dtype=np.int64)
        buf_lens.append(arr.size)
        if arr.size:
            buf_idx.append(arr)
        if len(buf_lens) == block_variants:
            yield emit()
            buf_idx, buf_lens = [], []
    if buf_lens:
        yield emit()


def restrict_window_to_sample_range(
    window_idx: np.ndarray,
    lens: np.ndarray,
    lo: int,
    hi: int,
) -> Tuple[np.ndarray, np.ndarray]:
    """Drop a window's carriers outside sample range ``[lo, hi)``.

    The per-host sample-range ingest contract (docs/ARCHITECTURE.md): a
    mesh host whose Gramian tiles cover sample rows/columns ``[lo, hi)``
    never needs carriers outside the union — every pair with an
    endpoint outside lands in a tile another host owns — so ingest may
    drop them before they reach the device feed, bit-identically for
    that host's tiles (pinned by test). Indices stay GLOBAL (the tile
    kernels re-base); ``lens`` is recomputed per variant so the window
    stays a valid CSR window. The full range ``(0, n)`` is a fast
    no-op (the single-controller case).
    """
    window_idx = np.asarray(window_idx, dtype=np.int64)
    lens = np.asarray(lens, dtype=np.int64)
    if window_idx.size == 0 or (
        lo <= 0 and (window_idx.size == 0 or hi > window_idx.max())
    ):
        return window_idx, lens
    keep = (window_idx >= lo) & (window_idx < hi)
    if bool(keep.all()):
        return window_idx, lens
    row_of = np.repeat(np.arange(lens.size, dtype=np.int64), lens)
    new_lens = np.bincount(
        row_of[keep], minlength=lens.size
    ).astype(np.int64)
    return window_idx[keep], new_lens


def blocks_from_csr(
    csr_iter,
    n_samples: int,
    block_variants: int = DEFAULT_BLOCK_VARIANTS,
) -> Iterator[np.ndarray]:
    """Stream per-shard CSR pairs into fixed-shape 0/1 int8 blocks.

    The vectorized twin of :func:`blocks_from_calls` for sources that can
    serve a shard's carrying lists as one ``(indices, offsets)`` pair
    (``stream_carrying_csr``): each emitted block is a single fancy-index
    scatter over the window's nonzeros instead of a Python loop over
    variants. Emits the same blocks bit-for-bit in the same order.

    ``csr_iter`` yields ``(indices, offsets)`` with ``offsets`` of length
    rows+1 (or None for empty shards, skipped).
    """
    for window_idx, lens in csr_windows(csr_iter, block_variants):
        _check_indices(window_idx, n_samples)
        yield _densify_window(window_idx, lens, n_samples, block_variants)


def _densify_window(
    window_idx: np.ndarray,
    lens: np.ndarray,
    n_samples: int,
    block_variants: int,
) -> np.ndarray:
    """One CSR window → one dense (n_samples, block_variants) 0/1 int8
    block. The ONE densify-from-window scatter: `blocks_from_csr` and
    the packed fallback both call it, so the byte-identical-fallback
    guarantee can't silently diverge between copies."""
    cols = np.repeat(np.arange(lens.size, dtype=np.int64), lens)
    x = np.zeros((n_samples, block_variants), dtype=np.int8)
    x[window_idx, cols] = 1
    return x


def packed_block_from_csr(
    window_idx: np.ndarray,
    lens: np.ndarray,
    n_samples: int,
    block_variants: int = DEFAULT_BLOCK_VARIANTS,
) -> np.ndarray:
    """One CSR window → one BIT-PACKED ``(n_samples, ⌈Vb/8⌉)`` block.

    The build stage of the native ingest engine (``ingest.build``): the
    native core scatters carrier bits straight from the window's
    ``(indices, lens)`` into packbits layout — no int8 densify
    intermediate, 8× less memory traffic than densify + ``np.packbits``
    — releasing the GIL for the whole scatter, which is what lets
    builder threads scale. Fallback without the ``.so``: the historical
    densify + packbits composition, byte-identical by construction
    (pinned by the differential fuzz suite).
    """
    from spark_examples_tpu import obs
    from spark_examples_tpu.native import load

    window_idx = np.ascontiguousarray(window_idx, dtype=np.int64)
    lens = np.ascontiguousarray(lens, dtype=np.int64)
    _check_indices(window_idx, n_samples)
    stride = (block_variants + 7) // 8
    lib = load()
    native = lib is not None
    mode = "native" if native else "python"
    t0 = time.perf_counter()
    with obs.span("ingest.build", mode=mode, variants=int(lens.size)):
        if native:
            offsets = np.zeros(lens.size + 1, dtype=np.int64)
            np.cumsum(lens, out=offsets[1:])
            out = np.zeros((n_samples, stride), dtype=np.uint8)
            rc = lib.csr_to_packed_blocks(
                window_idx.ctypes.data,
                offsets.ctypes.data,
                lens.size,
                n_samples,
                stride,
                out.ctypes.data,
            )
            if rc != 0:
                # Unreachable after _check_indices; double-guarded so a
                # corrupt window can never silently drop a carrier.
                raise ValueError(
                    f"sample index out of range for N={n_samples} "
                    "in native csr_to_packed_blocks"
                )
        else:
            out = np.packbits(
                _densify_window(
                    window_idx, lens, n_samples, block_variants
                ).astype(bool),
                axis=1,
            )
    _record_block_built(mode, time.perf_counter() - t0)
    return out


def _record_block_built(mode: str, seconds: float) -> None:
    from spark_examples_tpu import obs

    reg = obs.get_registry()
    reg.counter(
        "ingest_blocks_built_total",
        "Packed genotype blocks produced by the ingest engine",
    ).labels(mode=mode).inc()
    reg.histogram(
        "ingest_block_build_seconds",
        "Per-block build latency (CSR window -> packed block)",
    ).labels(mode=mode).observe(seconds)


def packed_blocks_from_csr(
    csr_iter,
    n_samples: int,
    block_variants: int = DEFAULT_BLOCK_VARIANTS,
    workers: int = 1,
    attempt: Optional[Callable[[Callable[[], np.ndarray], str], np.ndarray]] = None,
) -> Iterator[np.ndarray]:
    """Stream per-shard CSR pairs into BIT-PACKED blocks, ``workers``
    at a time.

    The multi-worker block production pipeline: windows are sliced
    sequentially (:func:`csr_windows` — composition fixed by arrival
    order), built into packed blocks by up to ``workers`` threads (the
    native scatter releases the GIL, so threads scale), and yielded in
    COMPLETION order when ``workers > 1`` — safe because the Gramian
    accumulates exact integer counts, so G is bit-identical under any
    block arrival order (pinned by test). ``workers <= 1`` is the
    serial in-order path, byte-identical to
    ``pack_indicator_block(b) for b in blocks_from_csr(...)``.

    ``attempt`` wraps each block build (a pure, idempotent function of
    its window) — the driver passes its retry/fault-seam wrapper so a
    builder worker dying mid-block is retried per policy instead of
    silently dropping the block.
    """
    if attempt is None:
        def attempt(thunk, _key):  # noqa: ANN001 — default: no seam
            return thunk()

    def build(numbered):
        i, (window_idx, lens) = numbered
        return attempt(
            lambda: packed_block_from_csr(
                window_idx, lens, n_samples, block_variants
            ),
            str(i),
        )

    numbered = enumerate(csr_windows(csr_iter, block_variants))
    if workers <= 1:
        for item in numbered:
            yield build(item)
        return
    from spark_examples_tpu.utils.concurrency import (
        completion_parallel_map,
    )

    yield from completion_parallel_map(build, numbered, workers)
