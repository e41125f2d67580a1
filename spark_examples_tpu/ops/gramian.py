"""Sample co-occurrence Gramian as MXU matmuls.

Semantics (reference ``VariantsPca.scala:170-191``): for each variant, every
unordered pair of samples that both carry a non-reference allele contributes
+1 to ``G[i, j]`` (and the diagonal counts each sample against itself). With
the per-variant sample-index lists densified to a 0/1 indicator block
``X ∈ {0,1}^(N_samples × V_variants)`` this is exactly ``G = X @ X.T`` — the
O(k²)-per-variant scalar loop of the reference becomes one batched matmul.

Counts are integers, so an f32 matmul of 0/1 operands is *exact* as long as
no entry of G exceeds 2^24 (16.7M co-occurring variants per sample pair) —
far beyond the all-autosomes 1000 Genomes scale (~40M variants total, but a
single pair co-occurring at every variant would still need f64/int paths;
``gramian_blockwise`` therefore accumulates into an f64-safe int32/float32
choice via ``accum_dtype``).

TPU notes: X is stored int8 host-side (HBM-friendly). By default the
per-block product rides the **integer MXU**: int8×int8→int32, then the exact
int32 counts are cast into the accumulator dtype (float32 by default, exact
below 2^24 total co-occurrences per pair). Measured on a real TPU v5 lite at
the bench shape (N=2504, V=65536, end-to-end blockwise stream including
host→device transfer; a round-3 builder capture, not a ledger line):

    int8 einsum 0.197s | f32 einsum 0.353s | bf16 0.312s |
    pallas dense 2.75s | pallas sym 2.19s

so int8 is 1.8× over f32 and both hand-written Pallas kernels lost to the
XLA einsum by ~10× end-to-end — the Pallas path was deleted on that
evidence (they remain in git history; the hardware bit-exactness suite had
them at parity numerically). ``SPARK_EXAMPLES_TPU_GRAMIAN=f32`` forces the
matmul itself into the accumulator dtype (escape hatch; observably
identical results either way — both paths are exact integer counts).
"""

from __future__ import annotations

import os
from functools import partial
from typing import Iterable

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "gang_gramian_blockwise",
    "gramian",
    "gramian_accumulate",
    "gramian_accumulate_packed",
    "gramian_blockwise",
    "mxu_cross_product",
    "mxu_cross_product_pair",
    "pack_indicator_block",
    "resolve_gramian_compute_dtype",
    "unpack_indicator_block",
]


def resolve_gramian_compute_dtype(x_dtype, out_dtype, compute_dtype=None):
    """Pick the MXU dtype for one Gramian call — OUTSIDE any jit trace.

    Every public entry point resolves the mode here before entering its
    jitted body, so ``SPARK_EXAMPLES_TPU_GRAMIAN`` is consulted (and
    validated) on every call rather than frozen into the first trace's
    cached executable. Policy: explicit ``compute_dtype`` wins; env
    ``f32`` forces the matmul into ``out_dtype``; env ``int8`` forces the
    integer MXU; default rides the integer MXU whenever X is stored int8.
    """
    if compute_dtype is not None:
        return compute_dtype
    forced = os.environ.get("SPARK_EXAMPLES_TPU_GRAMIAN", "")
    if forced not in ("", "auto", "int8", "f32"):
        raise ValueError(
            f"SPARK_EXAMPLES_TPU_GRAMIAN={forced!r}: expected 'auto', "
            "'int8', or 'f32'"
        )
    if forced == "f32":
        return out_dtype
    if forced == "int8" or x_dtype == jnp.int8:
        return jnp.int8
    return out_dtype


def mxu_cross_product(x, out_dtype, compute_dtype=None):
    """``X @ X.T`` in the fastest exact dtype path for 0/1 indicators.

    The single mode-policy seam shared by every Gramian entry point
    (single-device and sharded): int8-stored blocks ride the integer MXU
    (int8×int8→int32, 1.8× over f32 on TPU v5e — module docstring table)
    and the exact int32 product is cast to ``out_dtype``; anything else
    computes directly in ``out_dtype``. NOTE: when called inside a jit /
    shard_map trace with ``compute_dtype=None``, the env escape hatch is
    resolved at trace time — callers that want per-call env semantics
    must resolve via :func:`resolve_gramian_compute_dtype` outside the
    trace (all public entry points here and in ``parallel/sharded`` do).
    """
    return mxu_cross_product_pair(x, x, out_dtype, compute_dtype)


def mxu_cross_product_pair(a, b, out_dtype, compute_dtype=None):
    """``A @ B.T`` under the Gramian exact-dtype policy — the
    cross-tile form the pod-sparse dense step uses (each device
    multiplies its tile's ROW slice of X against its COLUMN slice).
    :func:`mxu_cross_product` is the ``a is b`` special case and
    delegates here, so the integer-MXU routing and the exactness
    argument live in exactly ONE body."""
    compute_dtype = resolve_gramian_compute_dtype(
        a.dtype, out_dtype, compute_dtype
    )
    af, bf = a.astype(compute_dtype), b.astype(compute_dtype)
    if compute_dtype == jnp.int8:
        prod = jnp.einsum(
            "nv,mv->nm", af, bf, preferred_element_type=jnp.int32
        )
        return prod.astype(out_dtype)
    return jnp.einsum("nv,mv->nm", af, bf, preferred_element_type=out_dtype)


@partial(jax.jit, static_argnames=("compute_dtype", "accum_dtype"))
def _gramian_jit(x, compute_dtype, accum_dtype):
    return mxu_cross_product(x, accum_dtype, compute_dtype)


def gramian(x, compute_dtype=None, accum_dtype=jnp.float32):
    """``G = X @ X.T`` for a 0/1 genotype-indicator block.

    Args:
      x: ``(n_samples, n_variants)`` array, any integer/float dtype with 0/1
        values (int8 preferred for storage).
      compute_dtype: dtype the matmul runs in on the MXU; ``None`` picks the
        measured-fastest exact path (int8 for int8 storage, modulo the env
        escape hatch).
      accum_dtype: dtype of the returned Gramian.

    Returns:
      ``(n_samples, n_samples)`` symmetric co-occurrence matrix.
    """
    compute_dtype = resolve_gramian_compute_dtype(
        x.dtype, accum_dtype, compute_dtype
    )
    return _gramian_jit(x, compute_dtype, accum_dtype)


@partial(jax.jit, static_argnames=("compute_dtype",), donate_argnums=(0,))
def _gramian_accumulate_jit(g, x_block, compute_dtype):
    return g + mxu_cross_product(x_block, g.dtype, compute_dtype)


def gramian_accumulate(g, x_block, compute_dtype=None):
    """One blockwise-accumulation step: ``G += X_blk @ X_blk.T``.

    This is the variant-axis streaming primitive (the reference's
    ``getSimilarityMatrixStream`` memory/shuffle tradeoff,
    ``VariantsPca.scala:248-279``, re-done TPU-style): the variant axis is
    unbounded while G stays fixed at N×N on device. ``g`` is donated so the
    accumulator updates in place in HBM.
    """
    compute_dtype = resolve_gramian_compute_dtype(
        x_block.dtype, g.dtype, compute_dtype
    )
    return _gramian_accumulate_jit(g, x_block, compute_dtype)


def pack_indicator_block(x_block: np.ndarray) -> np.ndarray:
    """Host-side bit-pack of a 0/1 indicator block: (N, V) → (N, ⌈V/8⌉).

    The variant axis is transfer-bound through any host→device link;
    0/1 indicators waste 7 of every 8 bits
    of an int8 block. ``np.packbits`` is C-speed and the pack overlaps
    the previous block's device matmul in the prefetch pipeline.

    PRECONDITION: values must be 0/1 indicators. Packing collapses any
    nonzero value to 1 (``astype(bool)``), which would silently corrupt a
    dosage-valued block (0/1/2) into a wrong Gramian. A strided subsample
    (≤64Ki elements, so the check never competes with packbits itself at
    the ~160 MB bench block size) is validated on every call; it cannot
    catch every stray value, so block producers own the full invariant.
    """
    x_block = np.asarray(x_block)
    if x_block.size:
        flat = x_block.reshape(-1)
        step = max(1, flat.shape[0] // 65536)
        sample = flat[::step]
        # Exact-0/1 check (not a range check): a fractional dosage like
        # 0.5 sits inside [0, 1] but still collapses to 1 under
        # astype(bool) — compare against the round-trip instead.
        if not np.array_equal(sample, sample.astype(bool)):
            bad_lo, bad_hi = sample.min(), sample.max()
            raise ValueError(
                "pack_indicator_block requires exact 0/1 indicator values; "
                f"got values in [{bad_lo}, {bad_hi}] (dosage-valued blocks "
                "must use the unpacked path)"
            )
    return np.packbits(x_block.astype(bool), axis=1)


def unpack_indicator_block(x_packed, n_bits: int):
    """Device-side unpack: (N, ⌈V/8⌉) uint8 → (N, n_bits) int8 0/1.

    A broadcasted shift-and-mask XLA fuses into the consumer; the
    transient (N, V) int8 is the same HBM footprint the unpacked path
    would have transferred anyway.
    """
    shifts = jnp.arange(7, -1, -1, dtype=jnp.uint8)
    bits = (x_packed[:, :, None] >> shifts) & jnp.uint8(1)
    return bits.reshape(x_packed.shape[0], -1)[:, :n_bits].astype(jnp.int8)


@partial(
    jax.jit,
    static_argnames=("n_bits", "compute_dtype"),
    donate_argnums=(0,),
)
def _gramian_accumulate_packed_jit(g, x_packed, n_bits, compute_dtype):
    x = unpack_indicator_block(x_packed, n_bits)
    return g + mxu_cross_product(x, g.dtype, compute_dtype)


def gramian_accumulate_packed(g, x_packed, n_bits=None, compute_dtype=None):
    """``G += X_blk @ X_blk.T`` from a bit-packed block (8× less transfer).

    ``x_packed`` is :func:`pack_indicator_block` output (host or device);
    ``n_bits`` is the true variant count V of the block (default: all
    8·⌈V/8⌉ columns — the pad bits packbits appends are zero and inert in
    the Gramian, so the default is safe). Bit-identical to the unpacked
    path; measured on-chip before being offered (PERFORMANCE.md).
    """
    if n_bits is None:
        n_bits = 8 * x_packed.shape[1]
    compute_dtype = resolve_gramian_compute_dtype(
        jnp.int8, g.dtype, compute_dtype
    )
    return _gramian_accumulate_packed_jit(g, x_packed, n_bits, compute_dtype)


@partial(
    jax.jit,
    static_argnames=("compute_dtype",),
    donate_argnums=(0,),
)
def _gang_accumulate_jit(g, x_stack, compute_dtype):
    """One gang step: ``G[b] += X[b] @ X[b].T`` for every cohort b —
    the per-cohort Gramian step vmapped over the leading batch axis, so
    B small-cohort accumulations ride ONE dispatch and one executable
    (the MXU analogue of request coalescing)."""
    return g + jax.vmap(
        lambda xb: mxu_cross_product(xb, g.dtype, compute_dtype)
    )(x_stack)


def gang_gramian_blockwise(
    windows: Iterable,
    remaps,
    n_max: int,
    block_variants: int = 8192,
    accum_dtype=jnp.float32,
    compute_dtype=None,
):
    """Batched Gramians for B cohorts from ONE full-frame window stream.

    ``windows`` yields full-frame ``(indices, lens)`` CSR windows (the
    ``csr_windows``/``windows_from_calls`` shape); ``remaps`` is one
    int array per cohort mapping full-frame sample index → that
    cohort's dense index (< 0 drops the carrier). Every window is
    scattered into one ``(B, n_max, width)`` int8 stack (cohorts
    shorter than ``n_max`` zero-pad — inert rows) and accumulated by
    the vmapped batch step: ONE jit cache entry for the whole gang,
    device round-trips amortized B-fold. Each ``G[b]``'s top-left
    ``(n_b, n_b)`` corner is bit-identical to that cohort's serial
    accumulation — exact integer counts, any composition (pinned by
    tests).

    Returns the host ``(B, n_max, n_max)`` f32 stack (callers slice
    per-cohort corners).
    """
    batch = len(remaps)
    if batch == 0:
        raise ValueError("gang_gramian_blockwise needs >= 1 cohort")
    remaps = [np.asarray(r, dtype=np.int64) for r in remaps]
    g = jnp.zeros((batch, n_max, n_max), dtype=accum_dtype)
    compute_dtype = resolve_gramian_compute_dtype(
        jnp.int8, accum_dtype, compute_dtype
    )
    for window_idx, lens in windows:
        window_idx = np.asarray(window_idx, dtype=np.int64)
        lens = np.asarray(lens, dtype=np.int64)
        # Fixed width = the block width: every full-size window hits
        # the same executable; only the tail window pays a second one.
        width = max(int(lens.size), 1)
        if width < block_variants:
            width = block_variants
        cols = np.repeat(np.arange(lens.size, dtype=np.int64), lens)
        stack = np.zeros((batch, n_max, width), dtype=np.int8)
        for b, remap in enumerate(remaps):
            mapped = remap[window_idx]
            keep = mapped >= 0
            stack[b][mapped[keep], cols[keep]] = 1
        g = _gang_accumulate_jit(g, stack, compute_dtype)
    return np.asarray(g)


def gramian_blockwise(
    blocks: Iterable[np.ndarray],
    n_samples: int,
    accum_dtype=jnp.float32,
    compute_dtype=None,
    device=None,
    packed: bool = False,
    prepacked: bool = False,
    prefetch_depth: int = 2,
):
    """Stream variant blocks through ``G += X_blk @ X_blk.T`` on device.

    Host generator → device accumulation; each block is transferred while the
    previous block's matmul runs (JAX dispatch is async, so transfer/compute
    overlap comes for free as long as blocks are pre-staged with
    ``jax.device_put``).

    Args:
      blocks: iterable of host ``(n_samples, v_blk)`` 0/1 arrays (ragged
        ``v_blk`` allowed; recompilation is avoided by padding upstream in
        :mod:`spark_examples_tpu.arrays.blocks`).
      n_samples: N — fixed by the callset index before any variant is read
        (reference ``VariantsCommon.scala:38-50``).
      prepacked: with ``packed=True``, the blocks are ALREADY
        ``pack_indicator_block`` output (uint8 bytes) — skip the host
        pack (callers that keep a packed cohort resident, and the
        native ingest engine's direct-packed block production).
      prefetch_depth: device-feed staging depth (``--prefetch-depth``):
        how many transferred blocks the double-buffered prefetch keeps
        ahead of the accumulating matmul.

    Returns:
      ``(N, N)`` device Gramian.
    """
    from spark_examples_tpu import obs
    from spark_examples_tpu.arrays.feed import device_prefetch

    g = jnp.zeros((n_samples, n_samples), dtype=accum_dtype)
    if device is not None:
        g = jax.device_put(g, device)
    if packed:
        # Pack on the host inside the prefetch generator so packing one
        # block overlaps the previous block's transfer+matmul. No width
        # side-channel needed: packbits pad bits unpack to zero columns,
        # which are inert in X @ X.T.
        def packed_stream():
            for xb in blocks:
                if prepacked:
                    yield xb
                else:
                    # Span closed BEFORE the yield: it must time the
                    # pack, not the consumer's turn of the generator.
                    with obs.span("ingest.pack"):
                        xp = pack_indicator_block(xb)
                    yield xp

        with obs.span("gramian_blockwise", packed=True):
            for i, xp in enumerate(
                device_prefetch(
                    packed_stream(), depth=prefetch_depth, device=device
                )
            ):
                # One span per accumulation DISPATCH (it times the
                # enqueue, not the device): its start is the
                # cold-stream acceptance anchor — the
                # first accumulate must begin while later shards are
                # still inside their ingest.fetch spans.
                with obs.span("gramian.accumulate", block=i):
                    g = gramian_accumulate_packed(
                        g, xp, compute_dtype=compute_dtype
                    )
        return g
    with obs.span("gramian_blockwise", packed=False):
        for i, xb in enumerate(
            device_prefetch(blocks, depth=prefetch_depth, device=device)
        ):
            with obs.span("gramian.accumulate", block=i):
                g = gramian_accumulate(g, xb, compute_dtype=compute_dtype)
    return g
