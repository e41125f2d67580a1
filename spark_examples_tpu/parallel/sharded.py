"""Sharded Gramian + PCoA under pjit/shard_map.

Two parallelism regimes, matching SURVEY.md §2.10's strategy table:

- **Variant-parallel (the DP/sequence-parallel analog).** V is huge, N
  moderate (the 1000-Genomes configs): each device holds a slice of the
  variant axis, computes a local partial ``X_loc @ X_loc.T``, and partial
  Gramians are ``psum``-reduced over the ring — the TPU-native replacement
  for the reference's per-task Breeze matrices + ``reduceByKey`` shuffle
  (VariantsPca.scala:184-191). Implemented with ``shard_map`` so the
  collective is explicit.

- **Sample-sharded (the TP analog).** N is huge (the synthetic 100k-sample
  stress config): G (N×N) lives 2D-sharded over (data, model); X rows are
  sharded and GSPMD inserts the all-gathers for ``X @ X.T``. The
  eigendecomposition at this scale cannot gather G to one device, so top-k
  eigenvectors come from :func:`topk_eig_randomized` — randomized subspace
  iteration whose only O(N²) op is ``C @ Q`` (shardable matmul); the
  (N, k+p) tall-skinny panel QR is done host-side-small per iteration.
"""

from __future__ import annotations

import functools
from functools import partial
from typing import Iterable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from spark_examples_tpu.ops.centering import double_center
from spark_examples_tpu.ops.gramian import (
    mxu_cross_product,
    pack_indicator_block,
    resolve_gramian_compute_dtype,
    unpack_indicator_block,
)
from spark_examples_tpu.ops.pcoa import (
    DEFAULT_RANDOMIZED_OVERSAMPLE,
    SpectralGapWarning,
    check_spectral_gap,
    normalize_eigvec_signs,
    randomized_panel_width,
)
from spark_examples_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS

__all__ = [
    "SpectralGapWarning",
    "addressable_sample_bounds",
    "gramian_blockwise_global",
    "gramian_variant_parallel",
    "gramian_variant_parallel_ring",
    "sample_bounds_of_indices",
    "sharded_gramian_blockwise",
    "sharded_gramian_blockwise_global",
    "sharded_pcoa",
    "sharded_sketch_finish",
    "sharded_sketch_panel",
    "sketch_tsqr",
    "sparse_sharded_gramian_blockwise",
    "EigNotConvergedError",
    "topk_eig_adaptive",
    "topk_eig_randomized",
]


def _mesh_axes(mesh: Mesh):
    has_model = MODEL_AXIS in mesh.axis_names
    return DATA_AXIS, (MODEL_AXIS if has_model else None)


def gramian_variant_parallel(x, mesh: Mesh, compute_dtype=None):
    """``G = psum_over_devices(X_loc @ X_loc.T)`` with X variant-sharded.

    ``x``: (N, V) with V divisible by the data-axis size. Returns G
    replicated (N small enough to replicate in this regime).
    """
    compute_dtype = resolve_gramian_compute_dtype(
        x.dtype, jnp.float32, compute_dtype
    )

    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=P(None, DATA_AXIS),
        out_specs=P(None, None),
    )
    def _local_gramian(x_loc):
        g_loc = mxu_cross_product(x_loc, jnp.float32, compute_dtype)
        return jax.lax.psum(g_loc, DATA_AXIS)

    return jax.jit(_local_gramian)(x)


def _axis_product(mesh: Mesh, spec: P) -> int:
    total = 1
    for entry in spec:
        if entry is None:
            continue
        for name in entry if isinstance(entry, tuple) else (entry,):
            total *= mesh.shape[name]
    return total


def _mesh_spans_processes(mesh: Mesh) -> bool:
    from spark_examples_tpu.parallel.mesh import mesh_spans_processes

    return mesh_spans_processes(mesh)


# dtype.num ↔ dtype for the cross-process dtype agreement (allgather moves
# int64 codes, not dtype objects); covers every block dtype a producer can
# legitimately emit (indicators, dosages, counts).
_DTYPE_BY_NUM = {
    np.dtype(t).num: np.dtype(t)
    for t in (
        np.bool_,
        np.int8,
        np.uint8,
        np.int16,
        np.int32,
        np.int64,
        np.float16,
        np.float32,
        np.float64,
    )
}


def _dtype_name(num: int):
    dt = _DTYPE_BY_NUM.get(num)
    return str(dt) if dt is not None else f"dtype.num={num}"


def _accumulate_blocks(
    blocks,
    n_samples: int,
    mesh: Mesh,
    x_sharding: NamedSharding,
    g_sharding: NamedSharding,
    compute_dtype,
    accum_dtype,
    packed: bool = False,
    prefetch_depth: int = 2,
):
    """Shared blockwise-Gramian core: pad, zero-init, accumulate, trim.

    The layout policy lives entirely in the two shardings; the feed policy
    follows the mesh — a process-spanning mesh gets the width/liveness-
    synced global stream, a host-local mesh a plain device prefetch (each
    host accumulating its own partial independently).

    The sample axis is padded to a multiple of the G-sharding axis sizes:
    N comes from the cohort's callset count, which is arbitrary, and
    device_put needs sharded dimensions to divide evenly. Zero rows are
    inert in X @ X.T (zero rows/cols of G), trimmed before returning.

    ``packed=True`` (for 0/1 indicator blocks only) bit-packs each block
    host-side after padding — 8× fewer bytes over every host→device feed,
    the same on-chip-measured win as the single-device path — and the
    jitted accumulator unpacks before the matmul. The packed column count
    is zero-byte-padded up to the variant-axis sharding divisor (zero
    bytes unpack to inert zero columns), and the synced global stream
    syncs on packed widths, which preserves its no-one-sided-deadlock
    guarantee (equal packed widths ⇒ equal global shapes).
    """
    from spark_examples_tpu.arrays.blocks import round_up_multiple

    n_padded = round_up_multiple(
        n_samples, _axis_product(mesh, g_sharding.spec)
    )
    v_spec = (
        x_sharding.spec[1] if len(x_sharding.spec) > 1 else None
    )
    v_div = _axis_product(mesh, P(v_spec))

    # Resolve the MXU dtype policy (incl. the SPARK_EXAMPLES_TPU_GRAMIAN
    # env escape hatch) OUTSIDE the trace, per accumulation stream —
    # mxu_cross_product's contract. The packed path always unpacks to
    # int8; the unpacked path resolves from the first block's REAL dtype
    # (a float dosage block must compute in float, not truncate to int8),
    # peeked here and pushed back onto the stream. On a process-spanning
    # mesh the peeked dtype is AGREED cross-process (same protocol shape
    # as the width sync): a process whose stream is empty would otherwise
    # default to int8 while float peers compile a different executable
    # around the same collectives — divergent programs, hang or garbage.
    # A real dtype mismatch raises on every process simultaneously.
    if packed:
        x_dtype = np.dtype(np.int8)
    else:
        blocks = iter(blocks)
        # The peek itself can raise (the producer runs ingest): on a
        # process-spanning mesh that raise must ride the agreement
        # collective below (code −2) like every later step's does in
        # _synced_block_stream, or one host dies pre-collective while
        # peers block in the allgather forever.
        peek_exc = None
        try:
            first = next(blocks, None)
        except Exception as e:  # noqa: BLE001 — re-raised below, synced
            peek_exc, first = e, None
        x_dtype = (
            np.dtype(np.int8) if first is None else np.asarray(first).dtype
        )
        if _mesh_spans_processes(mesh):
            from jax.experimental import multihost_utils

            # Raw num goes into the collective UNVALIDATED — validation
            # happens after the gather, on identical data everywhere, so
            # an unsupported dtype raises on every process together
            # instead of one process erroring pre-collective while peers
            # block in the allgather.
            if peek_exc is not None:
                local_num = -2
            else:
                local_num = -1 if first is None else x_dtype.num
            nums = np.asarray(
                multihost_utils.process_allgather(
                    np.array([local_num], np.int64)
                )
            ).ravel()
            failed = [i for i, v in enumerate(nums) if int(v) == -2]
            if failed:
                raise RuntimeError(
                    "block stream failed on process(es) "
                    f"{failed} while peeking the first block; raising "
                    "on every process together (a one-sided raise "
                    "would strand peers in the collective)"
                ) from peek_exc
            present = sorted({int(v) for v in nums if v >= 0})
            unsupported = [n for n in present if n not in _DTYPE_BY_NUM]
            if unsupported:
                raise ValueError(
                    "unsupported block dtype(s) in the pod-mode stream: "
                    f"{[_dtype_name(n) for n in unsupported]}; supported: "
                    f"{sorted(str(d) for d in _DTYPE_BY_NUM.values())}"
                )
            if len(present) > 1:
                raise ValueError(
                    "block dtypes differ across processes: "
                    f"{[_dtype_name(n) for n in present]}; "
                    "every host must stream the same block dtype"
                )
            if present:
                x_dtype = _DTYPE_BY_NUM[present[0]]
        if peek_exc is not None:
            # Single-process mesh: no peer to strand; surface directly.
            raise peek_exc
        if first is not None:
            import itertools

            blocks = itertools.chain((first,), blocks)
    compute_dtype = resolve_gramian_compute_dtype(
        x_dtype, accum_dtype, compute_dtype
    )

    @partial(jax.jit, donate_argnums=(0,), out_shardings=g_sharding)
    def _accum(g, xb):
        if packed:
            xb = unpack_indicator_block(xb, 8 * xb.shape[1])
        return g + mxu_cross_product(xb, g.dtype, compute_dtype)

    spans = _mesh_spans_processes(mesh)

    def padded_blocks():
        for block in blocks:
            xb = np.asarray(block)
            # Mid-stream dtype drift would retrace _accum with the WRONG
            # (stream-agreed) compute_dtype — e.g. float dosages truncated
            # through an int8 executable. Catch it locally here on
            # single-process meshes; the pod path defers to the per-step
            # synced check so the raise is never one-sided.
            if not packed and not spans and xb.dtype != x_dtype:
                raise ValueError(
                    f"block dtype changed mid-stream: {xb.dtype} after the "
                    f"stream was resolved as {x_dtype}; every block must "
                    "share one dtype"
                )
            if n_padded != n_samples:
                xb = np.pad(xb, ((0, n_padded - n_samples), (0, 0)))
            if packed:
                xb = pack_indicator_block(xb)
                cols = round_up_multiple(xb.shape[1], v_div)
                if cols != xb.shape[1]:
                    xb = np.pad(xb, ((0, 0), (0, cols - xb.shape[1])))
            yield xb

    g = jax.device_put(
        jnp.zeros((n_padded, n_padded), dtype=accum_dtype), g_sharding
    )
    # Zero-fill for drained streams must match the agreed block dtype, or
    # a drained float peer would feed int8 shards into the same global
    # array its neighbours build from float32.
    fill_dtype = np.dtype(np.uint8) if packed else x_dtype
    if spans:
        stream = _synced_block_stream(
            padded_blocks(), n_padded, x_sharding, fill_dtype=fill_dtype
        )
    else:
        from spark_examples_tpu.arrays.feed import device_prefetch

        stream = device_prefetch(
            padded_blocks(), depth=prefetch_depth, sharding=x_sharding
        )
    for xb in stream:
        g = _accum(g, xb)
    if n_padded == n_samples:
        return g
    # Trim as a (collective, when process-spanning) jit slice so the
    # result is never gathered to a host. No explicit out-sharding: the
    # trimmed dims need not divide the mesh axes; GSPMD keeps the layout
    # as close as the uneven shape allows.
    return jax.jit(lambda a: a[:n_samples, :n_samples])(g)


def sharded_gramian_blockwise(
    blocks: Iterable[np.ndarray],
    n_samples: int,
    mesh: Mesh,
    accum_dtype=jnp.float32,
    compute_dtype=None,
    packed: bool = False,
    prefetch_depth: int = 2,
):
    """Stream variant blocks into a mesh-sharded Gramian accumulator.

    G is laid out P(data, model) — 2D-sharded when the mesh has a model
    axis, row-sharded otherwise; X blocks arrive row-sharded P(data, None).
    GSPMD inserts the all-gather of X over the partial axis; accumulation
    stays in place in HBM (donated).
    """
    d_axis, m_axis = _mesh_axes(mesh)
    return _accumulate_blocks(
        blocks,
        n_samples,
        mesh,
        NamedSharding(mesh, P(d_axis, None)),
        NamedSharding(mesh, P(d_axis, m_axis)),
        compute_dtype,
        accum_dtype,
        packed=packed,
        prefetch_depth=prefetch_depth,
    )


def gramian_variant_parallel_ring(x, mesh: Mesh, compute_dtype=None):
    """Variant-parallel Gramian with an explicit ring reduction.

    Same math as :func:`gramian_variant_parallel` but the cross-device
    reduction is hand-scheduled as a ``ppermute`` ring instead of a single
    ``psum``: each step sends the running buffer to the next ICI neighbor
    and accumulates, so per-link traffic is balanced and each hop can
    overlap with other work — the ring-attention communication shape
    applied to the genomics "sequence" axis (the variant axis). XLA's
    psum typically lowers to an equivalent schedule on a ring ICI; this
    form makes the schedule explicit (and testable) as SURVEY.md §2.10's
    ring/blockwise analog.
    """
    compute_dtype = resolve_gramian_compute_dtype(
        x.dtype, jnp.float32, compute_dtype
    )
    n_dev = mesh.shape[DATA_AXIS]
    perm = [(j, (j + 1) % n_dev) for j in range(n_dev)]

    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=P(None, DATA_AXIS),
        out_specs=P(None, None),
        # After n_dev−1 ring hops every device holds the full sum, but the
        # static replication checker cannot prove it through ppermute.
        check_vma=False,
    )
    def _ring(x_loc):
        g_loc = mxu_cross_product(x_loc, jnp.float32, compute_dtype)

        def body(_, carry):
            acc, buf = carry
            buf = jax.lax.ppermute(buf, DATA_AXIS, perm)
            return acc + buf, buf

        acc, _ = jax.lax.fori_loop(0, n_dev - 1, body, (g_loc, g_loc))
        # Each device accumulated the same partials in a rotated order;
        # float non-associativity would make the "replicated" shards
        # bitwise-divergent (exact for 0/1 inputs, not for dosage-valued
        # X). Canonicalize by broadcasting device 0's copy so every shard
        # is identical regardless of input values.
        idx = jax.lax.axis_index(DATA_AXIS)
        return jax.lax.psum(
            jnp.where(idx == 0, acc, jnp.zeros_like(acc)), DATA_AXIS
        )

    return jax.jit(_ring)(x)


def gramian_blockwise_global(
    local_blocks,
    n_samples: int,
    mesh: Mesh,
    compute_dtype=None,
    accum_dtype=jnp.float32,
    packed: bool = False,
    prefetch_depth: int = 2,
):
    """Multi-controller blockwise Gramian: one mesh spanning every process.

    The TPU-pod execution model (multi-host GSPMD): each process ingests
    its own variant columns (its slice of the shard manifest) and
    contributes them as the process-local shard of a *global* block via
    ``jax.make_array_from_process_local_data``; the variant axis is sharded
    over all mesh axes, G stays replicated, and XLA emits the cross-chip
    reduction over ICI/DCN — no host-side gather of G at all (unlike
    :func:`spark_examples_tpu.parallel.distributed.allreduce_gramian`,
    which merges host-local partials through host memory).

    Hosts may ingest different numbers of blocks; every block step is a
    collective, so liveness and block width are synchronized per block
    with a tiny host allgather — a process whose stream is exhausted
    feeds zero columns (inert in the Gramian) at the peers' width until
    all streams drain, and a width mismatch raises on every process
    simultaneously (never a one-sided deadlock).
    """
    return _accumulate_blocks(
        local_blocks,
        n_samples,
        mesh,
        NamedSharding(mesh, P(None, tuple(mesh.axis_names))),
        NamedSharding(mesh, P(None, None)),
        compute_dtype,
        accum_dtype,
        packed=packed,
        prefetch_depth=prefetch_depth,
    )


def _synced_block_stream(
    local_blocks, n_samples: int, x_sharding, fill_dtype=np.int8
):
    """Per-step width/liveness-synced global blocks from per-process streams.

    Factored from the pod-mode accumulators: every process learns every
    peer's block width (−1 = exhausted) BEFORE any collective compute, so
    width mismatches raise on ALL processes together (one process raising
    alone would leave peers deadlocked in the next collective) and an
    exhausted process zero-fills at the peers' width until all streams
    drain (zero columns are inert in the Gramian).

    The same message carries each block's dtype.num: the upfront
    agreement in ``_accumulate_blocks`` only sees FIRST blocks, so a
    mid-stream dtype divergence (or a coordinated mid-stream switch away
    from the dtype the executable was compiled for) must be caught per
    step — again on every process simultaneously, from identical
    gathered data.

    Producer exceptions ride the same message (width code −2): the
    upstream generator runs host-side validation (e.g.
    ``pack_indicator_block``'s 0/1-indicator check) whose raise would
    otherwise fire on ONE process before its allgather post, leaving
    peers blocked in the collective forever. Instead the failing process
    posts −2 and every process raises together, the failing one chaining
    its original exception.
    """
    from jax.experimental import multihost_utils

    expected_num = fill_dtype.num
    it = iter(local_blocks)
    while True:
        exc = None
        try:
            block = next(it, None)
        except Exception as e:  # noqa: BLE001 — synced below, see docstring
            exc, block = e, None
        if exc is not None:
            w, num, rows = -2, -1, -1
        elif block is None:
            w, num, rows = -1, -1, -1
        else:
            block = np.asarray(block)
            w, num, rows = (
                int(block.shape[1]),
                block.dtype.num,
                int(block.shape[0]),
            )
        peer_info = np.asarray(
            multihost_utils.process_allgather(
                np.array([w, num, rows], np.int64)
            )
        ).reshape(-1, 3)
        failed = [
            i for i, (x, _, _) in enumerate(peer_info) if int(x) == -2
        ]
        if failed:
            # exc is None on healthy peers — `from None` is a no-op there.
            raise RuntimeError(
                "block stream failed on process(es) "
                f"{failed}; raising on every process together (a "
                "one-sided raise would strand peers in the next "
                "collective)"
            ) from exc
        # Row counts ride the same message: widths/dtypes can agree while
        # one process's block has the wrong sample count — that would pass
        # this sync and then diverge one-sided inside the collective
        # accumulate (rows are the UNsharded dim, inferred from local
        # data). n_samples here is the caller's padded N.
        bad_rows = sorted(
            {int(r) for x, _, r in peer_info if x >= 0 and r != n_samples}
        )
        if bad_rows:
            raise ValueError(
                f"block row counts {bad_rows} differ from the padded "
                f"sample count {n_samples}; every host must stream "
                "blocks over the full (padded) sample axis"
            )
        live = sorted({int(x) for x, _, _ in peer_info if x >= 0})
        if not live:
            return
        bad_nums = sorted(
            {int(n) for x, n, _ in peer_info if x >= 0 and n != expected_num}
        )
        if bad_nums:
            raise ValueError(
                "block dtype diverged mid-stream: got "
                f"{[_dtype_name(n) for n in bad_nums]} where the stream "
                f"was resolved as {_dtype_name(expected_num)}; every host "
                "must stream one dtype for the whole accumulation"
            )
        if len(live) > 1:
            raise ValueError(
                "block widths differ across processes in the same step: "
                f"{live}; every host must stream fixed-width blocks "
                "(blocks_from_calls pads) with the same --block-variants"
            )
        width = live[0]
        if block is None:
            block = np.zeros((n_samples, width), fill_dtype)
        yield jax.make_array_from_process_local_data(
            x_sharding, np.asarray(block)
        )


def sharded_gramian_blockwise_global(
    local_blocks,
    n_samples: int,
    mesh: Mesh,
    compute_dtype=None,
    accum_dtype=jnp.float32,
    packed: bool = False,
    prefetch_depth: int = 2,
):
    """Pod-mode blockwise Gramian with G *sample-sharded* over the mesh.

    The 100k-sample stress regime (BASELINE.md config #5): N is too large
    to replicate G per device (100k² f32 = 40 GB), so G lives 2D-sharded
    ``P(data, model)`` across the whole multi-process mesh while each
    process feeds its own variant columns — the combination the reference
    could not reach at all (its per-task dense matrix capped it near 50k
    samples in 20 GB heaps, VariantsPca.scala:176-177). Per-step sync and
    zero-fill semantics are identical to :func:`gramian_blockwise_global`;
    the only difference is the output layout, which GSPMD propagates into
    the einsum (each device builds its own G tile from the gathered block
    columns — the block all-gather rides ICI, G never moves).

    Returns G still sharded; downstream :func:`sharded_pcoa` consumes it
    without ever gathering at large N.
    """
    d_axis, m_axis = _mesh_axes(mesh)
    return _accumulate_blocks(
        local_blocks,
        n_samples,
        mesh,
        NamedSharding(mesh, P(None, tuple(mesh.axis_names))),
        NamedSharding(mesh, P(d_axis, m_axis)),
        compute_dtype,
        accum_dtype,
        packed=packed,
        prefetch_depth=prefetch_depth,
    )


def sample_bounds_of_indices(index_slices, n: int):
    """``(lo, hi)`` union of the sample ranges a tile set touches.

    ``index_slices`` are the per-device ``(row_slice, col_slice)`` pairs
    of an ``addressable_devices_indices_map`` over the (n, n) Gramian: a
    host whose tiles cover rows R and columns C only ever reads carrier
    indices inside ``R ∪ C`` — every pair with either endpoint outside
    the union lands in a tile some OTHER host owns. This is the per-host
    sample-range ingest contract (docs/ARCHITECTURE.md): ingest may
    drop carriers outside the bounds before they ever reach the device
    feed, bit-identically (pinned by test).
    """
    lo, hi = n, 0
    for row_sl, col_sl in index_slices:
        for sl in (row_sl, col_sl):
            start = sl.start if sl.start is not None else 0
            stop = sl.stop if sl.stop is not None else n
            lo, hi = min(lo, start), max(hi, stop)
    if hi <= lo:
        return 0, n
    return lo, hi


def addressable_sample_bounds(mesh: Mesh, g_sharding, n: int):
    """This process's sample-range bounds for a sharded (n, n) Gramian."""
    index_map = g_sharding.addressable_devices_indices_map((n, n))
    return sample_bounds_of_indices(index_map.values(), n)


@functools.lru_cache(maxsize=64)
def _sparse_tile_kernels(
    mesh: Mesh,
    d_axis,
    m_axis,
    n_padded: int,
    tile_rows: int,
    tile_cols: int,
    accum_name: str,
    compute_name: str,
    mirror: bool = False,
):
    """Compiled kernel set (tile scan, GSPMD dense fallback, pod dense
    tile step, symmetric-mirror finalizer, int8 MXU tile update) for one
    (mesh, padded-N, dtype, mirror) geometry — cached on the hashable
    geometry key. ``jax.jit`` caches by function identity, so
    building these as fresh closures per accumulation call would
    re-trace and re-compile the shard_map program on EVERY call (the
    bench sweep's repeats and per-job driver runs would measure XLA
    compilation, not accumulation); the lru_cache pins one executable
    per geometry.

    ``mirror=True`` (square tile grids on the pod path) exploits G's
    symmetry: an off-diagonal tile is exactly its transpose partner's
    transpose, so each partner computes only HALF — the upper device
    its tile's top row-slab, the lower device its right column-slab
    (complementary under transposition, so the pair's work splits
    evenly across the two owning processes instead of idling one) —
    and one final ``ppermute`` swap + transpose reassembles both
    tiles, bit-exactly (pure copies of exact integer counts). On a g×g
    grid this removes the g(g−1)/2 redundant off-diagonal tile
    computations the pair-space tiling otherwise duplicates across the
    diagonal: the dense route halves its off-diagonal MXU work
    (the scan and MXU tile updates are index-driven, so there the
    masking only keeps the partition consistent). The all_gather stays
    unconditional on every device — no collective ever sits inside a
    skipped branch.
    """
    from spark_examples_tpu.ops.gramian import mxu_cross_product_pair
    from spark_examples_tpu.ops.sparse import (
        pair_count_panel,
        scatter_pairs_chunked,
    )

    compute_dtype = jnp.dtype(compute_name)
    g_sharding = NamedSharding(mesh, P(d_axis, m_axis))

    def _grid_pos():
        d_idx = jax.lax.axis_index(d_axis)
        m_idx = (
            jax.lax.axis_index(m_axis)
            if m_axis is not None
            else jnp.int32(0)
        )
        return d_idx, m_idx

    half = tile_rows // 2  # mirror slab split (tiles square there)

    def _tile_range(idx, start, size):
        # Global sample indices → this tile's frame; outside it, the
        # out-of-bounds sentinel ``size`` (dropped downstream).
        return jnp.where((idx >= start) & (idx < start + size),
                         idx - start, size)

    def _tile_frames(idx):
        # Re-base global carrier indices into this device's row and
        # column frames; anything outside the tile becomes an
        # out-of-bounds sentinel that the scatter and the panel builds
        # drop. Tiles partition the (i, j) pair space, so the union
        # over devices is exactly one +1 per co-occurring pair — the
        # dense path's count.
        d_idx, m_idx = _grid_pos()
        li = _tile_range(idx, d_idx * tile_rows, tile_rows)
        lj = _tile_range(idx, m_idx * tile_cols, tile_cols)
        if mirror:
            # Off-diagonal slab partition: the upper partner owns its
            # top row-slab, the lower its right column-slab; the rest
            # is OOB here and reconstructed by the final mirror.
            li = jnp.where(
                jnp.logical_and(d_idx < m_idx, li >= half),
                tile_rows,
                li,
            )
            lj = jnp.where(
                jnp.logical_and(d_idx > m_idx, lj < half),
                tile_cols,
                lj,
            )
        return li, lj

    def mesh_tile_scatter(g_tile, idx):
        return scatter_pairs_chunked(g_tile, *_tile_frames(idx))

    scatter = jax.jit(
        jax.shard_map(
            mesh_tile_scatter,
            mesh=mesh,
            in_specs=(P(d_axis, m_axis), P(None, None)),
            out_specs=P(d_axis, m_axis),
        ),
        donate_argnums=(0,),
    )

    @partial(jax.jit, donate_argnums=(0,), out_shardings=g_sharding)
    def mesh_tile_dense(g, xp):
        xb = unpack_indicator_block(xp, 8 * xp.shape[1])
        return g + mxu_cross_product(xb, g.dtype, compute_dtype)

    @partial(jax.jit, donate_argnums=(0,), static_argnames=("n_cols",))
    def mesh_tile_mxu(g, pairs, n_cols=None):
        # The int8 MXU tile update: each device builds the carrier-count
        # panels of its own row range and its own column range from the
        # window's replicated carrier list (carriers outside a range drop
        # out) and adds P_r · P_cᵀ to its tile — the single-device MXU
        # update cut to a tile. Tiles partition the pair space, so the
        # union over devices is the window's X·Xᵀ, exactly; under
        # ``mirror`` the panels carry the scan's slab partition, and
        # their product is the masked pair scatter, exactly.
        # ``pairs`` is a (2, E) carrier list over ``n_cols`` sites
        # (carrier_pairs), or, with ``n_cols`` None, a pod step's
        # gathered (V, k) padded carrier matrix: site v's carriers are
        # row v.
        if n_cols is None:
            n_cols = pairs.shape[0]
            sites = jax.lax.broadcasted_iota(jnp.int32, pairs.shape, 0)
            pairs = jnp.stack([pairs.ravel(), sites.ravel()])

        def body(g_tile, pairs):
            li, lj = _tile_frames(pairs[0])
            rows = pair_count_panel(li, pairs[1], tile_rows, n_cols)
            cols = pair_count_panel(lj, pairs[1], tile_cols, n_cols)
            # Built apart from the contraction (fused, the panel build
            # would be recomputed per output tile of the matmul).
            rows, cols = jax.lax.optimization_barrier((rows, cols))
            return g_tile + mxu_cross_product_pair(
                rows, cols, g_tile.dtype, compute_dtype
            )

        return jax.shard_map(
            body,
            mesh=mesh,
            in_specs=(P(d_axis, m_axis), P(None, None)),
            out_specs=P(d_axis, m_axis),
        )(g, pairs)

    all_axes = tuple(mesh.axis_names)

    def _tile_dense_pod(g_tile, xp_loc):
        # The pod dense step as ONE explicit shard_map program: gather
        # the bit-PACKED panel bytes over every mesh axis (8× fewer
        # bytes over DCN than the unpacked X the GSPMD formulation
        # moved), unpack locally on each device, slice this tile's row
        # and column sample ranges, and accumulate the cross matmul.
        # The GSPMD version of this step forced an involuntary full
        # rematerialization of the (N, V, 8) unpack broadcast on the
        # process-spanning mesh (XLA spmd_partitioner warning) — ~14×
        # the runtime of this explicit form at the MULTICHIP bench
        # shape, measured in PERFORMANCE.md's decision log.
        # The all_gather runs UNCONDITIONALLY on every device (a
        # collective inside a skipped branch would strand peers); only
        # the local unpack + matmul shrinks under mirror.
        xp = jax.lax.all_gather(xp_loc, all_axes, axis=1, tiled=True)
        d_idx, m_idx = _grid_pos()
        r0 = d_idx * tile_rows
        c0 = m_idx * tile_cols

        def _mm(row_start, n_rows, col_start, n_cols):
            # Slice the PACKED panel's sample rows first (packing is
            # along the variant axis, so row slicing is exact), then
            # unpack only the two slabs — never the full (N, V) panel
            # per device (that full-unpack transient is the same waste
            # this program exists to remove from the GSPMD form).
            rows = unpack_indicator_block(
                jax.lax.dynamic_slice(
                    xp, (row_start, 0), (n_rows, xp.shape[1])
                ),
                8 * xp.shape[1],
            )
            cols = unpack_indicator_block(
                jax.lax.dynamic_slice(
                    xp, (col_start, 0), (n_cols, xp.shape[1])
                ),
                8 * xp.shape[1],
            )
            return mxu_cross_product_pair(
                rows, cols, g_tile.dtype, compute_dtype
            )

        if not mirror:
            return g_tile + _mm(r0, tile_rows, c0, tile_cols)
        # Slab partition (see docstring): upper partner computes only
        # its top row-slab, lower only its right column-slab — half the
        # MXU work each; diagonal tiles compute in full. lax.cond
        # executes one branch, so the skipped halves cost nothing.
        return jax.lax.cond(
            d_idx == m_idx,
            lambda g: g + _mm(r0, tile_rows, c0, tile_cols),
            lambda g: jax.lax.cond(
                d_idx < m_idx,
                lambda gg: gg.at[:half, :].add(
                    _mm(r0, half, c0, tile_cols)
                ),
                lambda gg: gg.at[:, half:].add(
                    _mm(r0, tile_rows, c0 + half, tile_cols - half)
                ),
                g,
            ),
            g_tile,
        )

    accum_dense_pod = jax.jit(
        jax.shard_map(
            _tile_dense_pod,
            mesh=mesh,
            in_specs=(P(d_axis, m_axis), P(None, all_axes)),
            out_specs=P(d_axis, m_axis),
        ),
        donate_argnums=(0,),
    )

    mirror_fill = None
    if mirror:
        grid_rows = mesh.shape[d_axis]
        grid_cols = mesh.shape[m_axis] if m_axis is not None else 1
        # Full involution over the tile grid: device (i, j) receives
        # tile (j, i) and reassembles its own tile from the slab
        # partition — the upper partner computed rows [0, half), so
        # transpose(partner) provides the lower partner's columns
        # [0, half), and vice versa; diagonal tiles are already whole.
        perm = [
            (i * grid_cols + j, j * grid_cols + i)
            for i in range(grid_rows)
            for j in range(grid_cols)
        ]

        def _mirror_tiles(g_tile):
            d_idx, m_idx = _grid_pos()
            swapped = jax.lax.ppermute(
                g_tile, (d_axis, m_axis), perm
            )
            st = jnp.swapaxes(swapped, 0, 1)
            # Upper tile: own rows [0, half) + partner's column slab
            # transposed (= rows [half, tile)). Lower tile: partner's
            # row slab transposed (= columns [0, half)) + own columns
            # [half, tile). Exact copies of exact integer counts.
            upper = jnp.concatenate(
                [g_tile[:half, :], st[half:, :]], axis=0
            )
            lower = jnp.concatenate(
                [st[:, :half], g_tile[:, half:]], axis=1
            )
            return jnp.where(
                d_idx == m_idx,
                g_tile,
                jnp.where(d_idx < m_idx, upper, lower),
            )

        mirror_fill = jax.jit(
            jax.shard_map(
                _mirror_tiles,
                mesh=mesh,
                in_specs=P(d_axis, m_axis),
                out_specs=P(d_axis, m_axis),
            ),
            donate_argnums=(0,),
        )

    return scatter, mesh_tile_dense, accum_dense_pod, mirror_fill, mesh_tile_mxu


@functools.lru_cache(maxsize=16)
def _sharded_zeros(shape, dtype_name: str, sharding):
    """A zero array made in place on each device of ``sharding`` — never
    whole on one device first (at N = 49,960 a whole f32 G is 10 GB)."""
    return jax.jit(
        lambda: jnp.zeros(shape, jnp.dtype(dtype_name)),
        out_shardings=sharding,
    )


@partial(jax.jit, static_argnames=("n",))
def _trim_square(a, n: int):
    return a[:n, :n]


# Route codes on the pod-sparse header wire (field 0 doubles as the
# liveness code): −2 producer exception, −1 stream exhausted, else the
# window's density-route decision.
_ROUTE_CODES = {"scatter": 0, "dense": 1}
_ROUTE_OF_CODE = {v: k for k, v in _ROUTE_CODES.items()}


def _synced_carrier_stream(
    windows,
    n_samples: int,
    n_padded: int,
    mesh: Mesh,
    density_threshold: float,
    dense_width: int,
    v_div: int,
    x_sharding,
    idx_sharding,
    pipeline_depth: int = 2,
    coalesce_variants: int = None,
):
    """Pipelined per-step header/carrier exchange of global windows from
    per-process CSR streams — the sparse twin of
    :func:`_synced_block_stream` (ROADMAP item 2's pod half), rebuilt as
    a depth-D pipeline over the host-side coordination-service exchange
    (:mod:`spark_examples_tpu.parallel.podstream`, ROADMAP item 3).

    Every sparse accumulation step on a process-spanning mesh runs one
    collective device program (the tile scatter / the pod dense tile
    matmul over the whole mesh), so per step every process FIRST agrees
    a tiny host header — ``[route/liveness code, k_max, variant rows,
    payload dtype.num, nnz, windows]`` — and only then enters the
    payload phase. The agreement used to ride device allgathers
    enqueued behind the previous window's scatter on each device's
    serial stream — collective latency serialized against compute. Now
    header, payload-confirm, and carrier exchange are pure host RPCs on
    a sync thread: window ``w+1``'s whole protocol step (including its
    densify/pack/carrier-padding host work) runs while window ``w``'s
    scatter executes on device, ``pipeline_depth`` slots ahead
    (``0`` = inline lockstep, the ablation mode). The failure-sync
    discipline carries over slot-by-slot:

    - a process whose stream is exhausted posts −1 and keeps feeding
      inert payloads (all-sentinel carrier rows, or zero packed columns
      on dense steps) until every stream drains — zero contributions
      are inert in the Gramian, so stragglers never strand peers;
    - a producer exception posts −2 and every process raises together
      at the SAME slot position, the failing one chaining its original
      exception (a one-sided raise would leave peers blocked in the
      next device collective forever). The per-shard retry seams run
      INSIDE the producer, upstream of this sync; post-header LOCAL
      payload construction failures are covered by the payload-confirm
      exchange before any payload moves, for every in-flight slot;
    - the density route is a per-step GLOBAL decision (both routes are
      collective device programs — half the pod cannot scatter while
      the other half matmuls): the header carries each process's local
      :func:`spark_examples_tpu.ops.sparse.window_route` decision and a
      divergent step raises on every process together (pin
      ``--sparse-density-threshold`` to 0 or large to force one route
      on heterogeneous cohorts);
    - carrier widths are NOT required to agree — ragged windows are the
      norm — instead every process pads to the power-of-two bucket of
      the GLOBAL max width (and to the global max variant-row count),
      so the collective scatter executable caches per geometry across
      hosts;
    - tiny scatter-route windows COALESCE into one gang per step
      (consecutive local windows until their variant-row total reaches
      ``coalesce_variants``; a dense-route window ends the gang and
      becomes its own step), so per-step exchange latency amortizes
      over many windows — bit-identical at any gang split (exact
      integer accumulation, pinned by tests).

    Scatter steps exchange the padded ``(rows, k_bucket)`` int32
    carrier matrices host-side (~d·N·V_blk integers — tiny next to the
    dense packed panels; a drained peer's inert all-sentinel block is
    synthesized locally from its header, zero bytes moved) and every
    device re-bases the concatenated global matrix into its tile frame
    for the same tile update; dense steps carry this process's
    packed panel columns into the pod dense tile program
    (packed-bytes all_gather inside the shard_map — see
    ``_sparse_tile_kernels``).

    Yields ``(route, global_payload, counts_fit_int8, local_nnz,
    local_variants, step, local_windows, stream_id)``; on scatter steps
    ``counts_fit_int8`` is the int8 fit check of the gathered matrix
    (:func:`spark_examples_tpu.ops.sparse.carrier_matrix_counts_fit_int8`),
    which every process runs on the same data. Device arrays are built
    HERE, on the consumer thread — the sync thread never touches jax,
    so the device collective launch order stays identical on every
    process.
    """
    from spark_examples_tpu import obs
    from spark_examples_tpu.arrays.blocks import (
        _check_indices,
        _densify_window,
        round_up_multiple,
    )
    from spark_examples_tpu.ops.gramian import pack_indicator_block
    from spark_examples_tpu.ops.sparse import (
        DEFAULT_POD_COALESCE_VARIANTS,
        _carrier_bucket,
        _note_pod_gang,
        _note_pod_sync,
        _pad_rows_for_scan,
        carrier_matrix_counts_fit_int8,
        dense_panel_width,
        padded_carrier_matrix,
        window_route,
    )
    from spark_examples_tpu.parallel.podstream import (
        PodSlot,
        PodWindowExchange,
        SlotPipeline,
    )
    from spark_examples_tpu.utils import collectivecheck

    if coalesce_variants is None:
        coalesce_variants = DEFAULT_POD_COALESCE_VARIANTS
    if pipeline_depth < 0:
        raise ValueError(
            f"--pod-pipeline-depth must be >= 0, got {pipeline_depth}"
        )
    # Resolved HERE, on the consumer thread: the sync thread must
    # never touch jax (the segfault-safety basis of the host-side
    # exchange design — see podstream's module docstring).
    world = jax.process_count()
    pid = jax.process_index()
    exchange = PodWindowExchange.open()
    if exchange is None:
        raise RuntimeError(
            "process-spanning sparse accumulation needs the "
            "jax.distributed coordination service for its host-side "
            "window exchange; initialize via parallel.distributed."
            "initialize_from_env (any multi-process jax run has it)"
        )

    it = iter(windows)
    pushback: list = []

    def _pull():
        if pushback:
            return pushback.pop()
        return next(it, None)

    def _gang():
        """This step's local windows: ``[]`` when drained, ONE
        dense-route window, or 1+ scatter-route windows coalesced until
        the variant-row total reaches ``coalesce_variants``."""
        first = _pull()
        if first is None:
            return [], None
        idx = np.asarray(first[0], dtype=np.int64)
        lens = np.asarray(first[1], dtype=np.int64)
        _check_indices(idx, n_samples)
        route = window_route(lens, n_samples, density_threshold)
        gang = [(idx, lens)]
        if route == "dense":
            return gang, route
        total = int(lens.size)
        while total < coalesce_variants:
            nxt = _pull()
            if nxt is None:
                break
            nidx = np.asarray(nxt[0], dtype=np.int64)
            nlens = np.asarray(nxt[1], dtype=np.int64)
            _check_indices(nidx, n_samples)
            if (
                window_route(nlens, n_samples, density_threshold)
                != "scatter"
            ):
                # A dense window ends the gang and becomes the NEXT
                # step — the route stays a per-step global decision.
                pushback.append((nidx, nlens))
                break
            gang.append((nidx, nlens))
            total += int(nlens.size)
        return gang, "scatter"

    state = {"step": 0}

    def _produce_step(step):
        exc = None
        gang: list = []
        code, k_max, rows, num, nnz, nwin = -1, -1, -1, -1, 0, 0
        try:
            gang, route_local = _gang()
            if gang:
                all_lens = [lens for _, lens in gang]
                code = _ROUTE_CODES[route_local]
                k_max = max(
                    (int(lens.max()) if lens.size else 0)
                    for lens in all_lens
                )
                rows = sum(int(lens.size) for lens in all_lens)
                nnz = sum(int(lens.sum()) for lens in all_lens)
                nwin = len(gang)
                # The PAYLOAD dtype rides the wire: int32 carrier
                # matrices on scatter steps, packed uint8 panels on
                # dense ones — agreed from identical gathered data so a
                # divergence raises everywhere.
                num = np.dtype(
                    np.int32 if route_local == "scatter" else np.uint8
                ).num
        except Exception as e:  # noqa: BLE001 — synced below, see docstring
            exc, code = e, -2
        # The collective-check backstop's enablement rides the header
        # (field 6) so the digest exchange below is an AGREED step: it
        # runs only when every process advertised it, and a
        # mixed-enablement pod degrades to unchecked instead of
        # desyncing on unexpected frames.
        check_flag = 1 if collectivecheck.collective_check_enabled() else 0
        with obs.span(
            "gramian.sparse.allgather",
            step=step,
            phase="header",
            stream=exchange.stream,
            processes=world,
        ):
            exchange.post_header(
                step,
                np.array(
                    [code, k_max, rows, num, nnz, nwin, check_flag],
                    np.int64,
                ),
            )
            peer_info = exchange.gather_headers(step, 7)
        failed = [
            i for i, row in enumerate(peer_info) if int(row[0]) == -2
        ]
        if failed:
            _note_pod_sync("producer-error")
            # exc is None on healthy peers — `from None` is a no-op
            # there.
            raise RuntimeError(
                "carrier stream failed on process(es) "
                f"{failed}; raising on every process together (a "
                "one-sided raise would strand peers in the next "
                "collective)"
            ) from exc
        live = peer_info[peer_info[:, 0] >= 0]
        if live.size == 0:
            _note_pod_sync("drained")
            exchange.close()
            return None
        routes = sorted({int(c) for c in live[:, 0]})
        if len(routes) > 1:
            _note_pod_sync("route-divergence")
            per_proc = {
                i: _ROUTE_OF_CODE[int(row[0])]
                for i, row in enumerate(peer_info)
                if int(row[0]) >= 0
            }
            raise ValueError(
                "sparse pod streams disagree on the density route "
                f"for the same step: {per_proc}; the route is a "
                "per-window GLOBAL decision (both routes are "
                "collective programs) — pin "
                "--sparse-density-threshold to one side for "
                "heterogeneous cohorts"
            )
        nums = sorted({int(n) for n in live[:, 3]})
        if len(nums) > 1:
            # The dtype is DERIVED from the agreed route today, so this
            # can only fire on a version-skewed pod (hosts running
            # different code deriving different payload dtypes for the
            # same route) — the cross-version guard, not a runtime data
            # check.
            _note_pod_sync("dtype-divergence")
            raise ValueError(
                "sparse pod payload dtypes diverged in the same "
                f"step: {[_dtype_name(n) for n in nums]}; every "
                "host must stream one payload dtype (the dtype "
                "derives from the agreed route — divergence means "
                "a version-skewed pod)"
            )
        route = _ROUTE_OF_CODE[routes[0]]
        g_rows = _pad_rows_for_scan(int(live[:, 2].max()))
        # Derived step geometry — the values every process computes
        # LOCALLY from the gathered (identical) headers: the carrier
        # bucket on scatter steps, the pow2 panel width on dense ones.
        # Pure arithmetic on agreed ints, so it runs outside the
        # payload try; pulled ahead of payload construction so the
        # collective-check digest can cover it before any payload
        # bytes move.
        bucket = 0
        g_dense = 0
        payload_num = nums[0]  # the agreed payload dtype (checked above)
        if route == "scatter":
            bucket = _carrier_bucket(int(live[:, 1].max()))
            geometry = (g_rows, bucket, world, n_padded, payload_num)
        else:
            g_dense = dense_panel_width(int(live[:, 2].max()), dense_width)
            geometry = (g_dense, v_div, world, n_padded, payload_num)
        # Collective-congruence backstop: every LIVE process enabled it
        # (agreed, from the gathered flag column — a drained process
        # evaluates the same gathered predicate and participates in the
        # exchange regardless of its own env, so the decision stays
        # congruent) → exchange a digest of this step's derived
        # (op, geometry) sequence and raise on every process together
        # at the first divergent step.
        if bool((live[:, 6] == 1).all()):
            digest = collectivecheck.step_digest(
                exchange.stream,
                step,
                [("header", (world, 7)), (route, geometry)],
            )
            with obs.span(
                "gramian.sparse.allgather",
                step=step,
                phase="check",
                stream=exchange.stream,
                processes=world,
            ):
                exchange.post_check(step, digest)
                digests = exchange.gather_checks(step)
            collectivecheck.verify_step_digests(step, digests, digest)
        # Local payload construction is host numpy work (carrier
        # padding, densify/pack) that can fail one-sided — e.g.
        # MemoryError on the densify at biobank widths — AFTER the
        # header sync has committed every peer to this step, so it runs
        # under its own try and the confirm exchange agrees success
        # before any payload moves: the same all-raise-together
        # discipline, per in-flight slot.
        payload_exc = None
        local = None
        try:
            if route == "scatter":
                if gang:
                    gidx = np.concatenate(
                        [idx for idx, _ in gang]
                    )
                    glens = np.concatenate(
                        [lens for _, lens in gang]
                    )
                    local = padded_carrier_matrix(
                        gidx,
                        glens,
                        sentinel=n_padded,
                        n_rows=g_rows,
                        k_bucket=bucket,
                    )
                # Drained stream: nothing to post — every peer
                # synthesizes this process's inert all-sentinel block
                # locally from its −1 header (zero bytes moved).
            else:
                # g_dense is the pow2 panel bucket of the GLOBAL max
                # row count (identical gathered data on every process
                # ⇒ identical width), derived above with the step
                # geometry: tail/small windows no longer pay the full
                # block width in inert MXU columns.
                if gang:
                    xb = _densify_window(
                        gang[0][0], gang[0][1], n_samples, g_dense
                    )
                else:
                    xb = np.zeros((n_samples, g_dense), dtype=np.int8)
                if n_padded != n_samples:
                    xb = np.pad(
                        xb, ((0, n_padded - n_samples), (0, 0))
                    )
                xp = pack_indicator_block(xb)
                cols = round_up_multiple(xp.shape[1], v_div)
                if cols != xp.shape[1]:
                    # Zero bytes unpack to inert zero columns; every
                    # process derives the same width from the same
                    # gathered header, so the global shape agrees.
                    xp = np.pad(xp, ((0, 0), (0, cols - xp.shape[1])))
                local = xp
        except Exception as e:  # noqa: BLE001 — synced just below
            payload_exc = e
        with obs.span(
            "gramian.sparse.allgather",
            step=step,
            phase="confirm",
            stream=exchange.stream,
            processes=world,
        ):
            exchange.post_confirm(step, payload_exc is None)
            confirm = exchange.gather_confirms(step)
        bad = [i for i, v in enumerate(confirm) if int(v) == -2]
        if bad:
            _note_pod_sync("producer-error")
            raise RuntimeError(
                "carrier payload construction failed on "
                f"process(es) {bad}; raising on every process "
                "together (a one-sided raise would strand peers "
                "in the payload collective)"
            ) from payload_exc
        gathered = None
        counts_fit_int8 = False
        if route == "scatter":
            with obs.span(
                "gramian.sparse.allgather",
                step=step,
                phase="carrier",
                stream=exchange.stream,
                processes=world,
            ):
                if local is not None:
                    exchange.post_payload(step, local)
                parts = []
                for p in range(world):
                    if p == pid:
                        parts.append(
                            local
                            if local is not None
                            else np.full(
                                (g_rows, bucket), n_padded, np.int32
                            )
                        )
                    elif int(peer_info[p, 0]) >= 0:
                        parts.append(
                            exchange.get_payload(
                                step, p, (g_rows, bucket)
                            )
                        )
                    else:
                        # Drained peer: synthesize its inert
                        # all-sentinel block locally — zero bytes
                        # moved for a peer with nothing to say.
                        parts.append(
                            np.full(
                                (g_rows, bucket), n_padded, np.int32
                            )
                        )
                gathered = np.concatenate(parts, axis=0)
            # From the gathered matrix, which every process holds
            # alike: the tile update it selects is congruent.
            counts_fit_int8 = carrier_matrix_counts_fit_int8(
                gathered, n_samples
            )
        _note_pod_sync("synced")
        _note_pod_gang(nwin)
        return PodSlot(
            step=step,
            route=route,
            gathered=gathered,
            local=local,
            nnz=nnz,
            variants=max(rows, 0),
            windows=nwin,
            counts_fit_int8=counts_fit_int8,
        )

    def _produce():
        step = state["step"]
        with obs.span(
            "gramian.sparse.slot",
            step=step,
            depth=pipeline_depth,
            stream=exchange.stream,
            processes=world,
        ):
            slot = _produce_step(step)
        state["step"] = step + 1
        return slot

    # Failure-path discipline around the pipeline: a SYNCHRONIZED
    # protocol failure (raised by next() — every process raised at the
    # same frame boundary, pipes provably clean) propagates as-is and
    # the mesh stays reusable (the chaos suite runs failing streams
    # back-to-back). A ONE-SIDED abandonment — this process's device
    # staging raising, or the consumer's loop body dying (lands here
    # as GeneratorExit at the yield) — leaves the sync thread possibly
    # blocked mid-read with peers' frames still on the pipes, so the
    # mesh is poisoned: a later stream must fail loudly instead of
    # desyncing on garbage (pod recovery = fail-stop + relaunch).
    pipe_iter = iter(SlotPipeline(_produce, pipeline_depth))
    while True:
        try:
            slot = next(pipe_iter)
        except StopIteration:
            return
        try:
            if slot.route == "scatter":
                gathered = slot.gathered
                payload = jax.make_array_from_callback(
                    gathered.shape,
                    idx_sharding,
                    lambda sl, _g=gathered: _g[sl],
                )
            else:
                payload = jax.make_array_from_process_local_data(
                    x_sharding, slot.local
                )
            item = (
                slot.route,
                payload,
                slot.counts_fit_int8,
                slot.nnz,
                slot.variants,
                slot.step,
                slot.windows,
                exchange.stream,
            )
        except BaseException:
            exchange.poison()
            raise
        try:
            yield item
        except BaseException:
            exchange.poison()
            raise


def sparse_sharded_gramian_blockwise(
    windows,
    n_samples: int,
    mesh: Mesh,
    accum_dtype=jnp.float32,
    density_threshold=None,
    block_variants=None,
    compute_dtype=None,
    pipeline_depth: int = 2,
    coalesce_variants=None,
):
    """Stream CSR carrier windows into a mesh-sharded (tiled) Gramian.

    The biobank-scale composition (ROADMAP item 2): G lives 2-D
    block-sharded ``P(data, model)`` over the mesh grid — each device
    owns one ``(N/rows, N/cols)`` tile, so N×N never materializes on any
    single device — and each window accumulates WITHOUT densifying:

    - sparse windows (density below the threshold,
      :func:`spark_examples_tpu.ops.sparse.window_route`) reach every
      tile under ``shard_map`` as a replicated carrier list: each
      device re-bases the global sample indices into its tile frame,
      maps out-of-tile carriers to an out-of-bounds sentinel, and
      accumulates exactly the pairs that land in its tile — on a TPU
      by the int8 MXU tile update where every carrier count fits int8,
      else by the OOB-drop scan
      (:func:`spark_examples_tpu.ops.sparse.window_scatter_path`). No
      collective at all — the carriers are ~d·N·V_blk integers, tiny
      next to the dense block they replace, and tiles partition the
      pair space.
    - dense windows densify + bit-pack onto the existing MXU
      accumulator with G kept in the same tiled layout (GSPMD gathers
      the block columns; G never moves — the
      :func:`sharded_gramian_blockwise_global` layout argument).

    Both routes add exact integer counts, so the result is bit-identical
    to the dense reference at any mesh shape and any window order
    (pinned by tests). On a single-controller mesh ingest is restricted
    to this process's sample-range bounds first
    (:func:`addressable_sample_bounds`) — the per-host sample-range
    contract; there the bounds are the full range and the restriction
    is a no-op.

    PROCESS-SPANNING meshes run the per-step carrier-allgather protocol
    (:func:`_synced_carrier_stream`, the sparse twin of
    :func:`_synced_block_stream`): each process feeds its own variant
    windows; per window a header allgather agrees liveness, the global
    carrier width bucket, and the density route (divergence raises on
    every process together — never a one-sided deadlock), then the
    padded carrier matrices allgather cross-host (~d·N·V_blk sparse
    integers per window instead of dense packed panels) and every
    device re-bases the concatenated global matrix into its tile frame
    for the same tile update — zero new N×N anywhere. Dense-route
    windows of a mixed stream ride the existing packed pod collective.
    Pod ingest ships FULL sample-range windows (each host is the source
    of its variants for every peer's tiles), so the sample-range
    restriction applies only to single-controller meshes.
    """
    from spark_examples_tpu import obs
    from spark_examples_tpu.arrays.blocks import (
        DEFAULT_BLOCK_VARIANTS,
        _check_indices,
        _densify_window,
        restrict_window_to_sample_range,
        round_up_multiple,
    )
    from spark_examples_tpu.ops.sparse import (
        DEFAULT_SPARSE_DENSITY_THRESHOLD,
        _carrier_bucket,
        _note_window,
        _pad_rows_for_scan,
        carrier_counts_fit_int8,
        carrier_pairs,
        dense_panel_width,
        padded_carrier_matrix,
        window_route,
        window_scatter_path,
    )

    if density_threshold is None:
        density_threshold = DEFAULT_SPARSE_DENSITY_THRESHOLD
    d_axis, m_axis = _mesh_axes(mesh)
    g_sharding = NamedSharding(mesh, P(d_axis, m_axis))
    n_padded = round_up_multiple(
        n_samples, _axis_product(mesh, g_sharding.spec)
    )
    grid_rows = mesh.shape[d_axis]
    grid_cols = mesh.shape[m_axis] if m_axis is not None else 1
    tile_rows = n_padded // grid_rows
    tile_cols = n_padded // grid_cols
    spans = _mesh_spans_processes(mesh)
    compute_dtype = resolve_gramian_compute_dtype(
        jnp.int8, accum_dtype, compute_dtype
    )
    width = block_variants or DEFAULT_BLOCK_VARIANTS
    # Square pod tile grids skip the strictly-lower (transpose-
    # redundant) tiles during accumulation and mirror once at the end —
    # see _sparse_tile_kernels. Pod-only: the host-local path's G may
    # feed further host-side merges (allreduce_gramian) per-tile.
    mirror = (
        spans and grid_rows == grid_cols and grid_rows > 1
    )
    (
        _tile_scan,
        _accum_dense,
        _accum_dense_pod,
        _mirror_fill,
        _tile_mxu,
    ) = _sparse_tile_kernels(
        mesh,
        d_axis,
        m_axis,
        n_padded,
        tile_rows,
        tile_cols,
        np.dtype(accum_dtype).name,
        np.dtype(compute_dtype).name,
        mirror,
    )

    def scatter_step(g, idx, counts_fit_int8):
        # A pod step's gathered carrier matrix: its bucket and its fit
        # are alike on every process, so every process takes the same
        # tile program.
        path = window_scatter_path(int(idx.shape[1]), counts_fit_int8)
        if path == "mxu-tile":
            return _tile_mxu(g, idx)
        return _tile_scan(g, idx)

    def scatter_window(g, window_idx, lens):
        # Host-local tiles: the int8 MXU tile update where the host has
        # checked every carrier count fits int8, else the tile scan.
        path = window_scatter_path(
            _carrier_bucket(int(lens.max()) if lens.size else 0),
            carrier_counts_fit_int8(window_idx, lens, n_samples),
        )
        if path == "mxu-tile":
            pairs = carrier_pairs(window_idx, lens, sentinel=n_padded)
            return _tile_mxu(
                g,
                jax.device_put(pairs, idx_sharding),
                n_cols=dense_panel_width(int(lens.size), width),
            )
        idx = padded_carrier_matrix(
            window_idx,
            lens,
            sentinel=n_padded,
            n_rows=_pad_rows_for_scan(lens.size),
        )
        return _tile_scan(g, jax.device_put(idx, idx_sharding))

    idx_sharding = NamedSharding(mesh, P(None, None))
    g = _sharded_zeros(
        (n_padded, n_padded), np.dtype(accum_dtype).name, g_sharding
    )()
    with obs.span("gramian.sparse.accumulate", n=n_samples, sharded=True):
        if spans:
            # Pod mode: every step is a collective device program, so
            # windows arrive through the pipelined synced carrier
            # stream — dense pod panels use the variant-axis-over-
            # everything layout and the explicit packed-allgather tile
            # program (_tile_dense_pod).
            x_sharding = NamedSharding(
                mesh, P(None, tuple(mesh.axis_names))
            )
            v_div = _axis_product(mesh, P(tuple(mesh.axis_names)))
            stream = _synced_carrier_stream(
                windows,
                n_samples,
                n_padded,
                mesh,
                density_threshold,
                width,
                v_div,
                x_sharding,
                idx_sharding,
                pipeline_depth=pipeline_depth,
                coalesce_variants=coalesce_variants,
            )
            for (
                route,
                payload,
                counts_fit_int8,
                nnz,
                n_variants,
                step,
                n_win,
                stream_id,
            ) in stream:
                with obs.span(
                    "gramian.sparse.window",
                    route=route,
                    nnz=nnz,
                    variants=n_variants,
                    step=step,
                    stream=stream_id,
                    windows=n_win,
                ):
                    if route == "scatter":
                        g = scatter_step(g, payload, counts_fit_int8)
                    else:
                        g = _accum_dense_pod(g, payload)
                _note_window(route, nnz, count=n_win)
            if _mirror_fill is not None:
                # One tile-swap ppermute + transpose reconstructs the
                # skipped strictly-lower tiles — exact copies, so G
                # stays bit-identical to the full computation.
                g = _mirror_fill(g)
        else:
            x_sharding = NamedSharding(mesh, P(d_axis, None))
            lo, hi = addressable_sample_bounds(
                mesh, g_sharding, n_padded
            )
            windows = iter(windows)
            while True:
                # The dispatching thread's wait for its next window, as
                # on the single-device stream; the last finds the end.
                with obs.span("feed.wait"):
                    window = next(windows, None)
                if window is None:
                    break
                window_idx, lens = window
                lens = np.asarray(lens)
                _check_indices(np.asarray(window_idx), n_samples)
                window_idx, lens = restrict_window_to_sample_range(
                    window_idx, lens, lo, hi
                )
                route = window_route(lens, n_samples, density_threshold)
                nnz = int(lens.sum())
                with obs.span(
                    "gramian.sparse.window",
                    route=route,
                    nnz=nnz,
                    variants=int(lens.size),
                ):
                    if route == "scatter":
                        g = scatter_window(g, window_idx, lens)
                    else:
                        dense_width = dense_panel_width(
                            int(lens.size), width
                        )
                        xb = _densify_window(
                            window_idx, lens, n_samples, dense_width
                        )
                        if n_padded != n_samples:
                            xb = np.pad(
                                xb, ((0, n_padded - n_samples), (0, 0))
                            )
                        xp = pack_indicator_block(xb)
                        g = _accum_dense(
                            g, jax.device_put(xp, x_sharding)
                        )
                _note_window(route, nnz)
    if n_padded == n_samples:
        return g
    return _trim_square(g, n_samples)


# The mesh finish's programs, at module level so that a second analysis
# traces and compiles nothing: each is cached on its static arguments.
# Every product runs at float32 matmul precision (a TPU's default runs
# f32 matmuls in bfloat16 passes, which stalls the iteration near 1e-4).


@jax.jit
def mesh_center(g):
    """Double-centre a (possibly mesh-sharded) Gramian in float32."""
    return double_center(g)


def _sweep(c, q, length):
    def body(q, _):
        y = c @ q  # the only O(N²) op — sharded with C
        q, _ = jnp.linalg.qr(y)
        return q, None

    q, _ = jax.lax.scan(body, q, None, length=length)
    return q


def _ritz_from(q, b):
    # Rayleigh–Ritz on the current subspace; (p, p) stays small.
    w, u = jnp.linalg.eigh(b)
    order = jnp.argsort(-jnp.abs(w))
    return q @ u[:, order], w[order]


@partial(jax.jit, static_argnames=("iters",))
def mesh_subspace_eig(c, q, iters):
    """``iters`` sweeps of subspace iteration, then Rayleigh–Ritz."""
    with jax.default_matmul_precision("float32"):
        q = _sweep(c, q, iters)
        return _ritz_from(q, q.T @ (c @ q))


@partial(jax.jit, static_argnames=("k", "max_iters", "chunk"))
def mesh_subspace_eig_adaptive(c, q, tol, k, max_iters, chunk):
    """Sweeps in chunks of ``chunk`` until every top-k Ritz pair's
    relative residual is below ``tol`` or ``max_iters`` are spent →
    ``(vecs, vals, iterations used, largest top-k residual)``."""
    with jax.default_matmul_precision("float32"):
        tiny = jnp.finfo(c.dtype).tiny

        def cond(state):
            _, _, it, rel = state
            return jnp.logical_and(~(rel < tol), it < max_iters)

        def body(state):
            q, _, it, _ = state
            q = _sweep(c, q, chunk)
            y = c @ q  # reused: residual check AND the final Ritz matrix
            b = q.T @ y
            w, u = jnp.linalg.eigh(b)
            order = jnp.argsort(-jnp.abs(w))
            uk, wk = u[:, order[:k]], w[order[:k]]
            # Standard eigenpair residual per top-k Ritz pair:
            # ‖C v − λ v‖ with v = q·u, C v = y·u — no extra O(N²) work.
            rk = y @ uk - (q @ uk) * wk
            rel = jnp.max(
                jnp.linalg.norm(rk, axis=0)
                / jnp.maximum(jnp.abs(wk), tiny)
            )
            return q, b, it + chunk, rel

        q, b, used, rel = jax.lax.while_loop(
            cond,
            body,
            (
                q,
                jnp.zeros((q.shape[1], q.shape[1]), c.dtype),
                jnp.int32(0),
                jnp.asarray(jnp.inf, c.dtype),
            ),
        )
        # b is the last chunk's q.T @ (c @ q): Rayleigh–Ritz without
        # recomputing the O(N²·p) product.
        vecs, vals = _ritz_from(q, b)
        return vecs, vals, used, rel


class EigNotConvergedError(RuntimeError):
    """The mesh finish's subspace iteration spent its iterations without
    bringing every top-k Ritz residual under the tolerance."""


# The mesh finish's defaults: the relative Ritz residual it stops on
# (float32 iteration reaches 1e-7 to 2e-6 on population-structure
# cohorts, so 1e-5 leaves room above that floor while bounding the
# eigenvector error at O(1e-5 / relative gap)); the sweeps between two
# residual checks (population structure converges in a few sweeps, but
# the coordinates keep improving for a few more after the residual first
# passes the tolerance, and a sweep costs one read of C); and the
# iteration cap past which it raises.
MESH_EIG_TOL = 1e-5
MESH_EIG_CHECK_EVERY = 10
MESH_EIG_MAX_ITERS = 100


def _eig_panel(c, k, oversample, seed, mesh):
    """The random starting panel, global where the mesh spans processes."""
    n = c.shape[0]
    # The k+1-values convention lives in ONE helper (ops/pcoa.py): the
    # panel must carry a Ritz value past index k-1 or the spectral-gap
    # check silently never fires and a flat-spectrum cohort's ambiguity
    # goes unreported.
    p = randomized_panel_width(n, k, oversample)
    q0 = jax.random.normal(jax.random.PRNGKey(seed), (n, p), dtype=c.dtype)
    if mesh is not None and jax.process_count() > 1:
        # Multi-controller: the panel must be a global (replicated) array
        # to enter a jit alongside the process-spanning C — every process
        # derives the identical panel from the same key.
        host_q0 = np.asarray(q0)
        q0 = jax.make_array_from_callback(
            host_q0.shape,
            NamedSharding(mesh, P(None, None)),
            lambda idx: host_q0[idx],
        )
    return q0


def _replicate_result(vecs, vals, mesh):
    if mesh is not None and jax.process_count() > 1:
        # The (N, k+p) panel result is small even at stress N; replicate it
        # so hosts can read coordinates without touching the sharded C.
        rep = NamedSharding(mesh, P(None, None))
        vecs = jax.jit(lambda a: a, out_shardings=rep)(vecs)
        vals = jax.jit(
            lambda a: a, out_shardings=NamedSharding(mesh, P(None))
        )(vals)
    return vecs, vals


def topk_eig_randomized(
    c,
    k: int,
    oversample: int = DEFAULT_RANDOMIZED_OVERSAMPLE,
    iters: int = 30,
    seed: int = 0,
    mesh: Mesh = None,
    timer=None,
    gap_warn_ratio: float = 0.95,
    tol: float = None,
    check_every: int = 5,
):
    """Top-|λ| eigenpairs of symmetric C by randomized subspace iteration.

    The sharded-eig path for N where a dense ``eigh`` is infeasible
    (SURVEY.md §7 hard-parts #3): every O(N²) op is a matmul against an
    (N, k+p) panel, which GSPMD shards with C; the per-iteration QR runs on
    the small replicated panel. Subspace iteration on C converges to the
    invariant subspace of the largest-|λ| eigenvalues (signs recovered via
    Rayleigh quotients), which is exactly the MLlib |λ|-ordering
    (see :mod:`spark_examples_tpu.ops.pcoa`). Every product runs at
    float32 matmul precision.

    Returns ``(vecs (N,k), vals (k,))`` ordered by |λ| descending, signs
    normalized.

    Accuracy: on realistic PCoA spectra (population-structure cohorts have
    a few dominant eigenvalues over a long tail) the subspace converges to
    ~2e-7 max coordinate error vs dense ``eigh`` within 10 iterations at
    N=2048 (measured; see tests). The 30-iteration default is headroom for
    flatter spectra; only near-degenerate λ₁≈λ₂ pairs need more.

    Degeneracy is detected, not silent: when |λ_{k+1}|/|λ_k| exceeds
    ``gap_warn_ratio`` the returned subspace is well-converged but any
    basis *within* a near-degenerate pair is rotation-ambiguous — for a
    dense ``eigh`` just as much as for this method (a weakly structured
    cohort has no well-defined PC2). A :class:`SpectralGapWarning` fires
    with the ratio, and the ratio lands in the stage-timer report when a
    ``timer`` is passed. The Ritz values needed for the check come free
    from the oversampled panel.

    ``tol`` (opt-in) makes the iteration count adaptive: see
    :func:`topk_eig_adaptive`, which this returns the pairs of (an
    unconverged adaptive run returns its last subspace here; the mesh
    finish raises instead).
    """
    if tol is not None:
        vecs, vals, _, _ = topk_eig_adaptive(
            c, k, tol, oversample=oversample, iters=iters, seed=seed,
            mesh=mesh, timer=timer, gap_warn_ratio=gap_warn_ratio,
            check_every=check_every,
        )
        return vecs, vals
    q0 = _eig_panel(c, k, oversample, seed, mesh)
    vecs, vals = _replicate_result(*mesh_subspace_eig(c, q0, iters), mesh)
    check_spectral_gap(np.asarray(vals), k, gap_warn_ratio, timer)
    return normalize_eigvec_signs(vecs[:, :k]), vals[:k]


def topk_eig_adaptive(
    c,
    k: int,
    tol: float,
    oversample: int = DEFAULT_RANDOMIZED_OVERSAMPLE,
    iters: int = 30,
    seed: int = 0,
    mesh: Mesh = None,
    timer=None,
    gap_warn_ratio: float = 0.95,
    check_every: int = 5,
):
    """:func:`topk_eig_randomized` with the iteration count adaptive →
    ``(vecs, vals, iterations used, largest top-k residual)``.

    The power sweep runs in chunks of ``check_every`` under
    ``lax.while_loop``, stopping once every top-k Ritz pair's relative
    residual ``‖C·v − λ·v‖/|λ|`` drops below ``tol``, or at the hard cap
    ``iters`` (rounded up to a whole chunk). The residual is the
    standard eigenpair criterion — it bounds eigenvector error at
    O(tol / gap), which is honest where Ritz-value stagnation is not
    (values converge at the square of the vector rate). The check reuses
    the chunk's own ``C @ q`` product, so its marginal cost is one
    power-iteration-equivalent per chunk, and the final Rayleigh–Ritz
    reuses the last chunk's small matrix rather than recomputing the
    O(N²·p) product. The chunked sweep applies the same operations in
    the same order as the fixed path, so an unconverged adaptive run
    (``tol=0``, ``iters`` a chunk multiple) yields the fixed path's
    subspace. The iteration count used lands in the stage-timer report.
    """
    q0 = _eig_panel(c, k, oversample, seed, mesh)
    chunk = max(1, min(check_every, iters))
    vecs, vals, used, rel = mesh_subspace_eig_adaptive(
        c, q0, tol, k=k, max_iters=iters, chunk=chunk
    )
    used, rel = int(used), float(rel)
    if timer is not None:
        timer.note(
            f"randomized eig: {used}/{iters} iterations "
            f"(tol={tol:g}, residual {rel:.2e})"
        )
    vecs, vals = _replicate_result(vecs, vals, mesh)
    check_spectral_gap(np.asarray(vals), k, gap_warn_ratio, timer)
    return normalize_eigvec_signs(vecs[:, :k]), vals[:k], used, rel


def sharded_pcoa(
    g,
    k: int,
    mesh: Mesh,
    dense_eigh_limit: int = 8192,
    timer=None,
    eig_tol: float = None,
):
    """Center + top-k eigenvectors of a (possibly mesh-sharded) Gramian.

    Small N: gather the centered matrix and run dense ``eigh`` (exact, the
    replicated-eigh fallback of SURVEY.md §7). Large N: keep C sharded and
    use randomized subspace iteration — at the stress scale C is never
    materialized on any single device or host. The iteration stops on
    the relative Ritz residual ``eig_tol`` (default
    :data:`MESH_EIG_TOL`) and raises :class:`EigNotConvergedError` after
    :data:`MESH_EIG_MAX_ITERS` without it, rather than return an
    unconverged answer.
    """
    from spark_examples_tpu import obs

    n = g.shape[0]
    with obs.span("finish.mesh.center", n=n):
        c = mesh_center(g)
    if n <= dense_eigh_limit:
        if not c.is_fully_addressable:
            # Process-spanning shards: replicate through a collective jit
            # (affordable by definition at dense-eigh N) so the host can
            # read it.
            c = jax.jit(
                lambda a: a, out_shardings=NamedSharding(mesh, P(None, None))
            )(c)
        c = jax.device_put(np.asarray(c))
        from spark_examples_tpu.ops.pcoa import (
            principal_components,
            topk_with_gap_check,
        )

        # One extra eigenpair so the gap past k is checkable — dense eigh
        # is exactly as rotation-ambiguous on a flat spectrum as the
        # randomized path, so it gets the same loud degeneracy detection.
        return topk_with_gap_check(
            lambda kk: principal_components(c, kk), k, n, timer=timer
        )
    tol = MESH_EIG_TOL if eig_tol is None else eig_tol
    with obs.span("finish.mesh.eig", n=n, k=k, tol=tol) as attrs:
        vecs, vals, used, rel = topk_eig_adaptive(
            c, k, tol, iters=MESH_EIG_MAX_ITERS, mesh=mesh, timer=timer,
            check_every=MESH_EIG_CHECK_EVERY,
        )
        attrs.update(iterations=used, residual=rel)
    if not rel < tol:
        raise EigNotConvergedError(
            f"mesh finish: the top-{k} Ritz residual is {rel:.3g} after "
            f"{used} iterations, over the tolerance {tol:g}; the "
            "coordinates have not converged (a near-degenerate spectrum "
            "needs a looser --eig-tol, or --precise)"
        )
    return vecs, vals


# -- Gramian-free sketch panels (ops/sketch.py's mesh half) ------------------


def _replicated_np(a) -> np.ndarray:
    """Host copy of a (possibly process-spanning) fully-replicated
    array. Every process holds the whole value under P(None, ...), so
    the local shard IS the global array — no collective needed."""
    if getattr(a, "is_fully_addressable", True):
        return np.asarray(a)
    return np.asarray(a.addressable_shards[0].data)


@functools.lru_cache(maxsize=None)
def _sketch_pod_dense_kernel(mesh: Mesh):
    """The dense-route pod step for the SKETCH panel as one explicit
    shard_map program: each device unpacks only ITS packed variant
    columns (the same variant-axis-over-everything payload layout the
    Gramian pod step ships), computes its local
    ``X_loc · (X_locᵀ · Ω̃)`` contribution, and one psum over every
    mesh axis replicates the window's full update — no (N, V) unpack
    broadcast anywhere (the GSPMD-rematerialization lesson of the
    Gramian's `_tile_dense_pod`)."""
    all_axes = tuple(mesh.axis_names)

    def _step(y_loc, xp_loc, om_loc):
        xb = unpack_indicator_block(
            xp_loc, 8 * xp_loc.shape[1]
        ).astype(y_loc.dtype)
        contrib = xb @ (xb.T @ om_loc)
        return y_loc + jax.lax.psum(contrib, all_axes)

    return jax.jit(
        jax.shard_map(
            _step,
            mesh=mesh,
            in_specs=(P(None, None), P(None, all_axes), P(None, None)),
            out_specs=P(None, None),
            check_vma=False,
        ),
        donate_argnums=(0,),
    )


def sketch_tsqr(y, mesh: Mesh):
    """Tall-skinny QR of a mesh-resident (n_padded, l) panel — the
    sketch finish's pod-scale factorization (ROADMAP item 2's
    "TSQR + small eig" half).

    Classic two-level TSQR under ``shard_map`` over EVERY mesh axis
    flattened: per-device thin QR of the local row block, an
    ``all_gather`` of the (l, l) R factors, one replicated QR of the
    stacked (D·l, l) ladder, and each device composes its Q block with
    its slice of the second-level Q. Returns ``(q, r)``: q row-sharded
    over the flattened device axis, r replicated. Requires
    ``n_padded / device_count ≥ l`` (callers fall back to a host QR
    below that — the panel is tiny there by definition)."""
    axes = tuple(mesh.axis_names)
    n_padded, l = int(y.shape[0]), int(y.shape[1])
    flat = P(axes, None)
    flat_sharding = NamedSharding(mesh, flat)

    def _local(y_loc):
        q1, r1 = jnp.linalg.qr(y_loc)
        rs = jax.lax.all_gather(r1, axes, axis=0, tiled=True)
        q2, r = jnp.linalg.qr(rs)
        # Flattened device index composed per-axis: tuple-valued
        # axis_index is newer than the jax floor this tree supports.
        i = jnp.int32(0)
        for name in axes:
            i = i * mesh.shape[name] + jax.lax.axis_index(name)
        q2_i = jax.lax.dynamic_slice(q2, (i * l, 0), (l, l))
        return q1 @ q2_i, r

    fn = jax.jit(
        jax.shard_map(
            _local,
            mesh=mesh,
            in_specs=flat,
            out_specs=(flat, P(None, None)),
            check_vma=False,
        )
    )
    y_flat = jax.jit(lambda a: a, out_shardings=flat_sharding)(y)
    return fn(y_flat)


def sharded_sketch_panel(
    windows_factory,
    n_samples: int,
    k: int,
    mesh: Mesh,
    oversample=None,
    power_iters=None,
    seed: int = 0,
    density_threshold=None,
    block_variants=None,
    pipeline_depth: int = 2,
    coalesce_variants=None,
):
    """Stream CSR carrier windows into a mesh-replicated (N, k+p)
    sketch panel — the ``--pca-mode sketch`` twin of
    :func:`sparse_sharded_gramian_blockwise` that never materializes
    an N×N tile anywhere (ROADMAP item 2's million-sample row).

    The panel is O(N·(k+p)) f32, so unlike G it REPLICATES over the
    mesh (P(None, None)); what the mesh buys is the window machinery —
    and the TSQR finish. Topologies:

    - single-controller mesh: host window loop, full sample range (the
      sketch updates every row per window, so the Gramian path's
      sample-range restriction must NOT apply);
    - process-spanning pod: the per-step carrier-allgather protocol
      (:func:`_synced_carrier_stream`) unchanged — headers, fencing,
      route sync, coalesced gangs, and collective-check digests all
      extend to sketch steps for free; scatter payloads feed the same
      OOB-drop panel scatter, dense payloads the explicit
      psum program (:func:`_sketch_pod_dense_kernel`);
    - host-local mesh on a multi-controller run: each host accumulates
      its manifest slice's partial panel and the partials merge over
      DCN (the dense tiers' allreduce shape, but on (N, l) panels).

    ``windows_factory`` returns a fresh iterator per call — each
    ``--sketch-power-iters`` pass re-streams the cohort with
    Ω ← orth(Y). Returns an :class:`~spark_examples_tpu.ops.sketch.
    SketchPanel` with host f64 panels (n_padded rows) and ``mesh`` set
    so the finish routes through :func:`sharded_sketch_finish`.
    """
    from spark_examples_tpu import obs
    from spark_examples_tpu.arrays.blocks import (
        DEFAULT_BLOCK_VARIANTS,
        _check_indices,
        _densify_window,
        round_up_multiple,
    )
    from spark_examples_tpu.ops.pcoa import (
        DEFAULT_SKETCH_POWER_ITERS,
        randomized_panel_width,
    )
    from spark_examples_tpu.ops.sketch import (
        _note_sketch_window,
        _sketch_dense_update,
        _sketch_scatter_update,
        gaussian_test_matrix,
        sketch_host_bytes,
    )
    from spark_examples_tpu.ops.sparse import (
        DEFAULT_SPARSE_DENSITY_THRESHOLD,
        _pad_rows_for_scan,
        dense_panel_width,
        padded_carrier_matrix,
        window_route,
    )

    if density_threshold is None:
        density_threshold = DEFAULT_SPARSE_DENSITY_THRESHOLD
    if oversample is None:
        oversample = DEFAULT_RANDOMIZED_OVERSAMPLE
    if power_iters is None:
        power_iters = DEFAULT_SKETCH_POWER_ITERS
    width = block_variants or DEFAULT_BLOCK_VARIANTS
    l = randomized_panel_width(n_samples, k, oversample)
    all_axes = tuple(mesh.axis_names)
    n_padded = round_up_multiple(
        n_samples, _axis_product(mesh, P(all_axes))
    )
    spans = _mesh_spans_processes(mesh)
    rep = NamedSharding(mesh, P(None, None))
    omega_cur = gaussian_test_matrix(n_samples, l, seed)
    row_sums = np.zeros(n_samples, dtype=np.float64)
    y_host = None
    for p in range(power_iters + 1):
        first = p == 0
        aug = _sketch_aug_padded(omega_cur, n_samples, n_padded, first)
        om_dev = jax.device_put(aug, rep)
        y = jax.device_put(
            jnp.zeros((n_padded, l + 1), dtype=jnp.float32), rep
        )
        with obs.span(
            "gramian.sketch.accumulate",
            n=n_samples,
            l=l,
            sharded=True,
            sketch_pass=p,
        ):
            if spans:
                x_sharding = NamedSharding(mesh, P(None, all_axes))
                v_div = _axis_product(mesh, P(all_axes))
                idx_sharding = NamedSharding(mesh, P(None, None))
                dense_pod = _sketch_pod_dense_kernel(mesh)
                stream = _synced_carrier_stream(
                    windows_factory(),
                    n_samples,
                    n_padded,
                    mesh,
                    density_threshold,
                    width,
                    v_div,
                    x_sharding,
                    idx_sharding,
                    pipeline_depth=pipeline_depth,
                    coalesce_variants=coalesce_variants,
                )
                for (
                    route,
                    payload,
                    _,
                    nnz,
                    n_variants,
                    step,
                    n_win,
                    stream_id,
                ) in stream:
                    with obs.span(
                        "gramian.sketch.window",
                        route=route,
                        nnz=nnz,
                        variants=n_variants,
                        step=step,
                        stream=stream_id,
                        windows=n_win,
                    ):
                        if route == "scatter":
                            y = _sketch_scatter_update(
                                y, om_dev, payload
                            )
                        else:
                            y = dense_pod(y, payload, om_dev)
                    _note_sketch_window(route, count=n_win)
            else:
                for window_idx, lens in windows_factory():
                    lens = np.asarray(lens)
                    _check_indices(
                        np.asarray(window_idx), n_samples
                    )
                    route = window_route(
                        lens, n_samples, density_threshold
                    )
                    nnz = int(lens.sum())
                    with obs.span(
                        "gramian.sketch.window",
                        route=route,
                        nnz=nnz,
                        variants=int(lens.size),
                    ):
                        if route == "scatter":
                            idx = padded_carrier_matrix(
                                window_idx,
                                lens,
                                sentinel=n_padded,
                                n_rows=_pad_rows_for_scan(
                                    lens.size
                                ),
                            )
                            y = _sketch_scatter_update(
                                y,
                                om_dev,
                                jax.device_put(idx, rep),
                            )
                        else:
                            xb = _densify_window(
                                window_idx,
                                lens,
                                n_samples,
                                dense_panel_width(
                                    int(lens.size), width
                                ),
                            )
                            if n_padded != n_samples:
                                xb = np.pad(
                                    xb,
                                    (
                                        (0, n_padded - n_samples),
                                        (0, 0),
                                    ),
                                )
                            y = _sketch_dense_update(
                                y,
                                om_dev,
                                jax.device_put(
                                    pack_indicator_block(xb), rep
                                ),
                            )
                    _note_sketch_window(route)
        y_np = _replicated_np(y).astype(np.float64)
        if not spans and jax.process_count() > 1:
            # Host-local mesh on a multi-controller run: each host fed
            # only its manifest slice — merge the partial panels.
            from spark_examples_tpu.parallel.distributed import (
                allreduce_gramian,
            )

            y_np = np.asarray(allreduce_gramian(y_np))
        if first:
            row_sums = y_np[:n_samples, -1].copy()
        y_host = y_np[:, :-1]
        y_host -= y_host[:n_samples].mean(axis=0, keepdims=True)
        y_host[n_samples:] = 0.0
        if p < power_iters:
            q, _ = np.linalg.qr(y_host[:n_samples])
            omega_cur = q.astype(np.float32)
    from spark_examples_tpu.ops.sketch import SketchPanel

    omega_final = np.zeros((n_padded, l), dtype=np.float64)
    omega_final[:n_samples] = omega_cur.astype(np.float64)
    omega_final[:n_samples] -= omega_final[:n_samples].mean(
        axis=0, keepdims=True
    )
    return SketchPanel(
        y=y_host,
        omega=omega_final,
        row_sums=row_sums,
        n=n_samples,
        k=k,
        l=l,
        seed=seed,
        power_iters=power_iters,
        mesh=mesh,
        host_peak_bytes=sketch_host_bytes(n_padded, l),
    )


def _sketch_aug_padded(
    omega: np.ndarray, n: int, n_padded: int, first: bool
) -> np.ndarray:
    """The streamed right-hand panel for mesh runs: centered Ω̃ over
    the n real rows, zero pad rows, plus the companion column (ones on
    the first pass — the row-sums/parity vector — zeros after, keeping
    one executable geometry across passes)."""
    aug = np.zeros((n_padded, omega.shape[1] + 1), dtype=np.float32)
    aug[:n, :-1] = omega - omega.mean(axis=0, keepdims=True)
    if first:
        aug[:n, -1] = 1.0
    return aug


def sharded_sketch_finish(panel, k: int):
    """The sketch Nyström finish on a mesh: device TSQR of the shifted
    panel (:func:`sketch_tsqr` over the pod), the (k+p)×(k+p) core on
    the host in f64, and one sharded matmul for the coordinates.
    Returns ``(coords (n_padded, l), vals (l,))`` — the caller
    (:func:`spark_examples_tpu.ops.sketch.sketch_eig`) trims, checks
    the spectral gap, and sign-normalizes."""
    from spark_examples_tpu.ops.sketch import _nystrom_core

    mesh = panel.mesh
    y, omega = panel.y, panel.omega
    norm = float(np.linalg.norm(y))
    if norm == 0.0:
        return np.zeros((panel.n, panel.l)), np.zeros(panel.l)
    nu = float(np.sqrt(panel.n) * np.finfo(np.float32).eps * norm)
    y_nu = y + nu * omega
    n_padded, l = y_nu.shape
    rows_loc = n_padded // _axis_product(
        mesh, P(tuple(mesh.axis_names))
    )
    b = omega.T @ y_nu
    if rows_loc >= l:
        q_dev, r_dev = sketch_tsqr(
            jax.device_put(
                y_nu.astype(np.float32),
                NamedSharding(mesh, P(None, None)),
            ),
            mesh,
        )
        r = _replicated_np(r_dev).astype(np.float64)
        u1, vals = _nystrom_core(r, b, nu)
        coords_dev = jax.jit(
            lambda qq, uu: qq @ uu,
            out_shardings=NamedSharding(mesh, P(None, None)),
        )(q_dev, jnp.asarray(u1.astype(np.float32)))
        coords = _replicated_np(coords_dev).astype(np.float64)
    else:
        # Fewer rows per device than panel columns: the TSQR local QR
        # shape contract breaks, and at that size the whole finish is
        # host change money.
        q, r = np.linalg.qr(y_nu)
        u1, vals = _nystrom_core(r, b, nu)
        coords = q @ u1
    return coords, vals
