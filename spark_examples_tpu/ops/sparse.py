"""Sparse-aware Gramian accumulation straight from CSR carrier windows.

The dense path (:mod:`spark_examples_tpu.ops.gramian`) densifies every
variant window into a 0/1 indicator block and rides the MXU — O(N²·V)
matmul work and an (N, V_blk) transient regardless of how empty the
block is. At biobank shape (N=100k-1M, ~98% zeros) that transient and
the matmul are the wall. The decomposition papers (arxiv 1909.00954,
arxiv 1808.03374) compute G = XᵀX from the sparse representation
without ever densifying; this module is that path for the 0/1
indicator Gramian:

    G[i, j] += |{v : i ∈ carriers(v) and j ∈ carriers(v)}|

accumulated by ONE device step per window, directly from the
``(indices, lens)`` CSR windows the ingest tier already produces
(:func:`spark_examples_tpu.arrays.blocks.csr_windows`) — what crosses to
the device is the window's carrier indices, never a densified or
bit-packed block.

Formulation: each window's ragged carrier lists are right-padded into a
``(V_blk, k_max)`` int32 index matrix with an out-of-range sentinel. The
device step (``_scatter_accumulate_jit``) then takes one of three forms,
chosen per window on the host by :func:`window_scatter_path`:

- the pair scatter (``scan``, every non-TPU backend, and a TPU window
  whose counts pass int8): ``+1`` at every ``(idx[v, a], idx[v, b])``
  pair with OOB-drop semantics, O(Σ k_v²) work (k_v = carriers of
  variant v), in fixed-size variant chunks under ``lax.scan`` so the
  update transient is bounded by ``chunk · k_max²`` — the CPU path and
  the reference the MXU updates are pinned against;
- the MXU update (``mxu``, single device, TPU, bucket ≤ 127): the
  window's ``(N, V_blk)`` int8 carrier-count panel is built on the
  device by comparing the index matrix against a sample iota
  (:func:`carrier_count_panel`), then ``G += X·Xᵀ`` runs on the int8
  MXU — O(N²·V_blk) work on the unit built for it (on a TPU v5e, 1.4 ms
  per 3,202 × 8,192 window at k_max = 64, where the scan takes 259 ms);
- the scatter-built MXU update (``mxu-tile``, TPU, any other window
  whose counts fit int8): the same panel scatter-built from the
  window's carrier list (:func:`pair_count_panel`) — the update every
  mesh tile takes, here over the whole G as one tile.

Sentinel entries vanish and duplicate carriers count multiply in every
form, and every update is an exact integer in f32 (the same exactness
argument as the dense path — bit-identical G, pinned by tests).

Density routing: genuinely dense windows (common variants) would pay
k_max² ≈ (dN)² per variant here while the MXU path pays N·V_blk — the
scatter loses above a few percent density. ``sparse_gramian_blockwise``
therefore routes each window by its own density: strictly below the
threshold it scatters straight from CSR; at or above it densifies +
bit-packs into the existing MXU accumulator. Both routes add exact
integer counts, so the mix is bit-identical to either pure path
(PERFORMANCE.md has the decision-log entry for the default).
"""

from __future__ import annotations

from functools import partial
from typing import Iterable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "DEFAULT_POD_COALESCE_VARIANTS",
    "DEFAULT_SPARSE_DENSITY_THRESHOLD",
    "MXU_MAX_CARRIER_BUCKET",
    "SCATTER_CHUNK_VARIANTS",
    "SCATTER_PATHS",
    "carrier_count_panel",
    "carrier_counts_fit_int8",
    "carrier_matrix_counts_fit_int8",
    "carrier_pairs",
    "dense_panel_width",
    "padded_carrier_matrix",
    "pair_count_panel",
    "scatter_path_rule",
    "scatter_pairs_chunked",
    "sparse_gramian_accumulate",
    "sparse_gramian_blockwise",
    "window_density",
    "window_route",
    "window_scatter_path",
]

# Dense/sparse switch: windows with density STRICTLY below this scatter
# straight from CSR; at or above it they densify onto the MXU path. The
# default is the measured CPU crossover region with margin — see the
# PERFORMANCE.md decision-log entry (sparse wins on work at any d < 1,
# but a scatter update costs ~10-100x a matmul MAC, so the honest
# crossover sits at a few percent density; biobank cohorts at ~2% sit
# under it, 1000-Genomes common variants at ~10% over it).
DEFAULT_SPARSE_DENSITY_THRESHOLD = 0.02

# Variant rows scattered per lax.scan step: bounds the broadcast update
# transient at chunk * k_max^2 elements (e.g. 256 * 256^2 f32 = 67 MB)
# instead of the whole window's V_blk * k_max^2.
SCATTER_CHUNK_VARIANTS = 256

# Pod-sparse gang coalescing target (the pipelined carrier-allgather
# protocol in parallel/sharded._synced_carrier_stream): consecutive
# scatter-route windows merge into one protocol step until their
# variant-row total reaches this, so tiny windows (tail windows, small
# shards) amortize one header + one carrier exchange instead of paying
# per-window exchange latency. Windows at the normal block width
# (DEFAULT_BLOCK_VARIANTS) already exceed it — coalescing only engages
# where it pays. 0/1 disables. Aligned with SCATTER_CHUNK_VARIANTS so a
# full gang fills at least one scan chunk.
DEFAULT_POD_COALESCE_VARIANTS = 256

# The MXU updates hold per-(sample, variant) carrier counts in int8: a
# count is at most the carrier bucket, so a bucket within this fits by
# its width alone.
MXU_MAX_CARRIER_BUCKET = 127

# The updates a scatter-route window can take (:func:`scatter_path_rule`):
# the ``path`` labels of ``sparse_scatter_path_windows_total``.
SCATTER_PATHS = ("scan", "mxu", "mxu-tile")

_MIN_CARRIER_BUCKET = 8


def window_density(lens: np.ndarray, n_samples: int) -> float:
    """nnz / (N · V) for one CSR window (0.0 for an empty window)."""
    lens = np.asarray(lens)
    if lens.size == 0 or n_samples == 0:
        return 0.0
    return float(lens.sum()) / (n_samples * lens.size)


def window_route(
    lens: np.ndarray, n_samples: int, density_threshold: float
) -> str:
    """``"scatter"`` | ``"dense"`` for one window — THE switch both the
    single-device and mesh-sharded accumulators consult, so the two can
    never disagree on a boundary case. Density exactly AT the threshold
    routes dense (the MXU side of the tie), pinned by test.

    Two gates, both required for scatter: the MEAN density (total work,
    O(Σk²) pairs) and the MAX per-variant carrier fraction — scatter
    cost and its update transient scale with k_max², so ONE common
    variant (k ≈ N/4) buried in an otherwise-rare window would blow the
    padded carrier matrix to k_bucket ≈ N while the mean density still
    whispers "sparse". Such a window routes dense, where the MXU cost
    is flat in k.
    """
    lens = np.asarray(lens)
    if window_density(lens, n_samples) >= density_threshold:
        return "dense"
    if (
        lens.size
        and n_samples
        and int(lens.max()) / n_samples >= density_threshold
    ):
        return "dense"
    return "scatter"


def scatter_path_rule(
    backend: str, k: int, counts_fit_int8: bool, single_device: bool
) -> str:
    """``"scan" | "mxu" | "mxu-tile"``: the update a scatter-route window
    takes on ``backend`` — the one rule of the scatter route.

    On a TPU a window whose (sample, site) carrier counts fit int8 takes
    an int8 MXU panel update: a single-device window with carrier bucket
    ``k`` at most :data:`MXU_MAX_CARRIER_BUCKET` the compare-chain panel
    (``"mxu"``), any other (a larger bucket, a host-local or a pod mesh
    tile) the panels scatter-built from its carriers (``"mxu-tile"``).
    A window whose counts pass int8, and every window off a TPU, takes
    the chunked scan.
    """
    if backend != "tpu" or not counts_fit_int8:
        return "scan"
    if single_device and k <= MXU_MAX_CARRIER_BUCKET:
        return "mxu"
    return "mxu-tile"


def window_scatter_path(
    k: int, counts_fit_int8: bool, single_device: bool = False
) -> str:
    """:func:`scatter_path_rule` on this process's backend, counted in
    ``sparse_scatter_path_windows_total{path}`` — host side, once per
    window, by the single-device, host-local mesh and pod accumulators
    alike. Every process of a pod passes values it holds alike (the
    agreed bucket, the check on the gathered carrier matrix), so their
    device programs stay congruent."""
    from spark_examples_tpu import obs

    path = scatter_path_rule(
        jax.default_backend(), k, counts_fit_int8, single_device
    )
    obs.get_registry().counter(
        "sparse_scatter_path_windows_total",
        "Scatter-route windows by the update they took (scan = the "
        "chunked lax.scan; mxu = the compare-chain carrier-count panel "
        "on the int8 MXU; mxu-tile = carrier-count panels scatter-built "
        "from the carriers, on the int8 MXU)",
    ).labels(path=path).inc()
    return path


def _carrier_bucket(k: int) -> int:
    """Round a window's max carrier count up to a power of two (min 8):
    the padded index matrix's column count is a static jit shape, so
    bucketing bounds executable count at O(log N) per block width."""
    bucket = _MIN_CARRIER_BUCKET
    while bucket < k:
        bucket *= 2
    return bucket


def dense_panel_width(rows: int, block_variants: int) -> int:
    """Padded variant width for one DENSE-route window's panel.

    Historically every dense window padded to the full block width so
    the packed MXU executable shape stayed stable — but that makes a
    512-variant window on an 8192-variant block pay 16× its MXU work in
    inert zero columns (measured dominant in the MULTICHIP pod bench,
    PERFORMANCE.md decision log). The power-of-two bucket (min 8, capped
    at the block width — ``csr_windows`` never yields wider) keeps the
    executable count O(log V) by the same argument as
    :func:`_carrier_bucket` while tail/small windows pay only their
    rounded size. Zero pad columns are inert, so G is bit-identical at
    any bucketing (pinned by the existing mixed-route pins)."""
    if rows >= block_variants:
        # Wider-than-block windows (only reachable through direct API
        # use — csr_windows caps at the block width) keep the exact
        # historical max(width, rows) behavior.
        return max(rows, 1)
    return min(_carrier_bucket(rows), block_variants)


def padded_carrier_matrix(
    window_idx: np.ndarray,
    lens: np.ndarray,
    sentinel: int,
    n_rows: Optional[int] = None,
    k_bucket: Optional[int] = None,
) -> np.ndarray:
    """One CSR window → a ``(n_rows, k_bucket)`` int32 carrier matrix.

    Row v holds variant v's carrier sample indices, right-padded with
    ``sentinel`` (any index ≥ the scatter target's row count — padded
    pairs are OOB and dropped by the kernel). ``n_rows`` pads the
    variant axis (tail windows, scan-chunk alignment); padded rows are
    all-sentinel and inert. ``k_bucket`` overrides the locally-derived
    power-of-two carrier bucket — the pod-sparse protocol passes the
    bucket of the GLOBAL max width so every process pads to one agreed
    geometry and the collective scatter executable caches per geometry
    across hosts, never per host. Pure vectorized numpy — this is host
    work on the ingest path, C-speed like the densify scatter it
    replaces.
    """
    lens = np.asarray(lens, dtype=np.int64)
    window_idx = np.asarray(window_idx, dtype=np.int64)
    rows = lens.size if n_rows is None else n_rows
    if rows < lens.size:
        raise ValueError(
            f"n_rows {rows} < window variant count {lens.size}"
        )
    k_local = int(lens.max()) if lens.size else 0
    if k_bucket is None:
        k_bucket = _carrier_bucket(k_local)
    elif k_bucket < k_local:
        raise ValueError(
            f"k_bucket {k_bucket} < window max carrier count {k_local}"
        )
    mat = np.full((rows, k_bucket), sentinel, dtype=np.int32)
    if window_idx.size:
        row_of = np.repeat(np.arange(lens.size, dtype=np.int64), lens)
        starts = np.zeros(lens.size, dtype=np.int64)
        np.cumsum(lens[:-1], out=starts[1:])
        pos = np.arange(window_idx.size, dtype=np.int64) - starts[row_of]
        mat[row_of, pos] = window_idx
    return mat


def scatter_pairs_chunked(g, row_idx, col_idx):
    """``g[row_idx[v,a], col_idx[v,b]] += 1`` for every (v, a, b) —
    out-of-bounds indices dropped.

    The ONE chunked-scan scatter body: the single-device kernel passes
    the carrier matrix as both operands; the mesh-tiled kernel passes
    tile-re-based row/column copies. Shared so a chunking or exactness
    change can never land in one copy and silently miss the other (the
    bit-identity contract the tests pin). Index arrays are
    ``(V_pad, k_bucket)`` with V_pad a multiple of the scan chunk; the
    scan bounds the broadcast update transient at
    ``chunk · k_bucket²``. Every update is an exact integer +1 in
    ``g.dtype`` — the same below-2^24 exactness contract as the dense
    accumulator.
    """
    one = jnp.asarray(1, g.dtype)
    shape = (
        row_idx.shape[0] // SCATTER_CHUNK_VARIANTS,
        SCATTER_CHUNK_VARIANTS,
        row_idx.shape[1],
    )

    def body(acc, chunk):
        ci, cj = chunk
        return (
            acc.at[ci[:, :, None], cj[:, None, :]].add(one, mode="drop"),
            None,
        )

    g, _ = jax.lax.scan(
        body, g, (row_idx.reshape(shape), col_idx.reshape(shape))
    )
    return g


def carrier_count_panel(idx, n_rows: int):
    """``X[n, v] = |{a : idx[v, a] = n}|`` as an ``(n_rows, V_pad)`` int8
    panel, built on the device from a ``(V_pad, k)`` carrier matrix.

    A compare against a sample iota, summed over the carrier axis: no
    scatter unit and no sort. Sentinel entries (≥ ``n_rows``) match no
    row and drop out, as the scatter's ``mode="drop"`` does; a duplicate
    carrier counts more than once, as scatter-add does — so ``X·Xᵀ`` is
    the pair scatter's ``Σ_v r_v r_vᵀ`` exactly. A count is at most k,
    which the rule keeps within int8 (:data:`MXU_MAX_CARRIER_BUCKET`).

    The sum is a chain of k elementwise adds that fuses into one loop: on
    a TPU v5e it builds a 3,202 × 8,192 panel from k = 64 in 0.94 ms,
    where a ``reduce`` over the broadcast (k, N, V) compare takes
    1.47 ms and an ``.at[].add`` scatter of the V·k entries 4.42 ms —
    why a single-device window within the bucket keeps this build rather
    than :func:`pair_count_panel`'s.
    """
    rows = jax.lax.broadcasted_iota(jnp.int32, (n_rows, idx.shape[0]), 0)
    x = jnp.zeros(rows.shape, jnp.int8)
    for carriers in idx.T:
        x += (rows == carriers).astype(jnp.int8)
    return x


def carrier_pairs(
    window_idx: np.ndarray, lens: np.ndarray, sentinel: int
) -> np.ndarray:
    """One CSR window → a ``(2, nnz_bucket)`` int32 list of its carriers.

    Row 0 holds each carrier's sample index, row 1 the column of its
    site within the window, in CSR order; the tail past the window's
    nnz carries ``sentinel`` samples (dropped by the panel build). The
    length is nnz rounded up to a power of two, so what crosses to the
    device, and the panel build it feeds (:func:`pair_count_panel`),
    scale with the window's carriers — not with its sites times its
    largest carrier count, as :func:`padded_carrier_matrix` does.
    """
    lens = np.asarray(lens, dtype=np.int64)
    window_idx = np.asarray(window_idx)
    nnz = int(window_idx.size)
    pairs = np.full((2, _carrier_bucket(nnz)), sentinel, dtype=np.int32)
    pairs[0, :nnz] = window_idx
    pairs[1, :nnz] = np.repeat(
        np.arange(lens.size, dtype=np.int32), lens
    )
    return pairs


def carrier_counts_fit_int8(
    window_idx: np.ndarray, lens: np.ndarray, n_samples: int
) -> bool:
    """Does every (sample, site) carrier count of the window fit int8?

    A count is a carrier's repeats within one site, so only a site with
    more than :data:`MXU_MAX_CARRIER_BUCKET` carriers can pass it, and
    only if some carrier repeats more than that many times there. Those
    sites' entries are sorted by (site, sample) and the longest run of
    one key is the largest count; every other site is within int8 by
    its length alone.
    """
    lens = np.asarray(lens, dtype=np.int64)
    big = lens > MXU_MAX_CARRIER_BUCKET
    if not big.any():
        return True
    keys = np.repeat(np.flatnonzero(big).astype(np.int64), lens[big])
    keys *= n_samples
    keys += np.asarray(window_idx)[np.repeat(big, lens)]
    keys.sort()
    edges = np.flatnonzero(np.diff(keys)) + 1
    if edges.size + 1 == keys.size:
        return True  # no carrier repeats within a site
    runs = np.diff(np.concatenate([[0], edges, [keys.size]]))
    return int(runs.max()) <= MXU_MAX_CARRIER_BUCKET


def carrier_matrix_counts_fit_int8(mat: np.ndarray, n_samples: int) -> bool:
    """:func:`carrier_counts_fit_int8` for a padded carrier matrix
    (:func:`padded_carrier_matrix`, or a pod step's gathered matrix),
    whose entries at or past ``n_samples`` are pad. A bucket of at most
    :data:`MXU_MAX_CARRIER_BUCKET` fits by its width alone."""
    mat = np.asarray(mat)
    if mat.shape[1] <= MXU_MAX_CARRIER_BUCKET:
        return True
    live = mat < n_samples
    return carrier_counts_fit_int8(mat[live], live.sum(axis=1), n_samples)


def pair_count_panel(samples, sites, n_rows: int, n_cols: int):
    """``X[n, v] = |{e : samples[e] = n, sites[e] = v}|`` as an
    ``(n_rows, n_cols)`` int8 panel — built on the device by one
    scatter-add of the carrier list (:func:`carrier_pairs`), O(nnz).

    Samples outside ``[0, n_rows)`` (the pad sentinel, carriers of
    another tile's range mapped past the end by the caller) drop out; a
    repeated carrier counts more than once, as the pair scatter does.
    The caller has checked that no count passes int8
    (:func:`carrier_counts_fit_int8`).
    """
    return (
        jnp.zeros((n_rows, n_cols), jnp.int8)
        .at[samples, sites]
        .add(jnp.int8(1), mode="drop")
    )


@partial(
    jax.jit,
    donate_argnums=(0,),
    static_argnames=("path", "compute_dtype", "n_cols"),
)
def _scatter_accumulate_jit(
    g, carriers, path="scan", compute_dtype=None, n_cols=None
):
    """``g[idx[v,a], idx[v,b]] += 1`` for every (v, a, b) — OOB dropped.

    ``path`` is the window's update (:func:`window_scatter_path`): the
    chunked scan or the compare-chain panel (``"mxu"``) of the padded
    carrier matrix ``carriers``, or the panel scatter-built from the
    ``(2, E)`` carrier list ``carriers`` (:func:`carrier_pairs`) over
    ``n_cols`` sites (``"mxu-tile"``); the panels are contracted on the
    MXU in ``compute_dtype``
    (:func:`spark_examples_tpu.ops.gramian.mxu_cross_product`) —
    bit-identical whichever.
    """
    if path == "scan":
        return scatter_pairs_chunked(g, carriers, carriers)
    from spark_examples_tpu.ops.gramian import mxu_cross_product

    if path == "mxu":
        x = carrier_count_panel(carriers, g.shape[0])
    else:
        x = pair_count_panel(carriers[0], carriers[1], g.shape[0], n_cols)
    # The barrier keeps the panel build out of the contraction's
    # fusion: fused, the TPU recomputes the panel for every output
    # tile (XLA's cost model: about 4× the two steps apart).
    x = jax.lax.optimization_barrier(x)
    return g + mxu_cross_product(x, g.dtype, compute_dtype)


def _pad_rows_for_scan(rows: int) -> int:
    """Variant-axis padding so the scan chunking divides evenly."""
    from spark_examples_tpu.arrays.blocks import round_up_multiple

    return round_up_multiple(max(rows, 1), SCATTER_CHUNK_VARIANTS)


def sparse_gramian_accumulate(g, window_idx, lens):
    """One sparse accumulation step: scatter a CSR window into G.

    ``g`` is the ``(N, N)`` device accumulator (donated — updates in
    place in device memory); the window is host CSR ``(indices, lens)``.
    Returns the updated G. Bit-identical to densifying the window and
    running ``gramian_accumulate`` (pinned by tests), whichever update
    :func:`window_scatter_path` picks for it.
    """
    from spark_examples_tpu.ops.gramian import resolve_gramian_compute_dtype

    lens = np.asarray(lens)
    n = g.shape[0]
    n_rows = _pad_rows_for_scan(lens.size)
    path = window_scatter_path(
        _carrier_bucket(int(lens.max()) if lens.size else 0),
        carrier_counts_fit_int8(window_idx, lens, n),
        single_device=True,
    )
    if path == "mxu-tile":
        carriers = carrier_pairs(window_idx, lens, sentinel=n)
    else:
        carriers = padded_carrier_matrix(
            window_idx, lens, sentinel=n, n_rows=n_rows
        )
    compute_dtype = (
        None
        if path == "scan"
        else resolve_gramian_compute_dtype(jnp.int8, g.dtype)
    )
    return _scatter_accumulate_jit(
        g,
        carriers,
        path=path,
        compute_dtype=compute_dtype,
        n_cols=n_rows if path == "mxu-tile" else None,
    )


def _note_window(route: str, nnz: int, count: int = 1) -> None:
    """Per-window telemetry shared by the single-device and mesh
    accumulators (one registration site per metric, GL003). ``count``
    is the number of CSR windows this accumulation step carried — the
    pod protocol's coalesced gangs fold several windows into one step,
    and a pod step fed purely by inert padding (this process drained,
    peers still live) carries zero."""
    from spark_examples_tpu import obs

    reg = obs.get_registry()
    reg.counter(
        "sparse_gramian_windows_total",
        "CSR windows accumulated by the sparse-aware Gramian engine",
    ).labels(route=route).inc(count)
    reg.counter(
        "sparse_gramian_nnz_total",
        "Genotype carriers (nonzeros) accumulated by the sparse engine",
    ).inc(nnz)


def _note_pod_gang(n_windows: int) -> None:
    """Pod-sparse coalescing telemetry: how many local CSR windows one
    protocol step carried, labeled by whether they rode a multi-window
    gang (``mode="gang"``) or a solo step (``mode="solo"``) — the label
    set ``validate_trace._LABELED_COUNTERS`` enforces (GL003). One
    registration site; inert (zero-window) steps are not noted."""
    if n_windows <= 0:
        return
    from spark_examples_tpu import obs

    obs.get_registry().counter(
        "sparse_pod_coalesced_windows_total",
        "Local CSR windows entering pod-sparse protocol steps, by "
        "gang/solo coalescing outcome",
    ).labels(mode="gang" if n_windows > 1 else "solo").inc(n_windows)


def _note_pod_sync(outcome: str) -> None:
    """Per-step pod-sparse sync telemetry (the carrier-allgather
    protocol in ``parallel/sharded._synced_carrier_stream``): one
    registration site, outcome ∈ {synced, drained, producer-error,
    route-divergence, dtype-divergence} — the label set
    ``validate_trace._LABELED_COUNTERS`` enforces (GL003)."""
    from spark_examples_tpu import obs

    obs.get_registry().counter(
        "sparse_pod_sync_total",
        "Pod-sparse per-window sync steps (header + carrier allgather) "
        "by outcome",
    ).labels(outcome=outcome).inc()


def sparse_gramian_blockwise(
    windows: Iterable[Tuple[np.ndarray, np.ndarray]],
    n_samples: int,
    accum_dtype=jnp.float32,
    density_threshold: float = DEFAULT_SPARSE_DENSITY_THRESHOLD,
    block_variants: Optional[int] = None,
    device=None,
):
    """Stream CSR windows into a single-device G, routing per density.

    ``windows`` yields ``(indices, lens)`` pairs (``csr_windows``
    output). Sparse windows scatter straight from CSR; dense windows
    take the historical densify → bit-pack → MXU route (padded to
    ``block_variants`` so the packed executable shape stays stable).
    The mix is bit-identical to the pure dense path — both routes add
    exact integer counts.
    """
    from spark_examples_tpu import obs
    from spark_examples_tpu.arrays.blocks import (
        DEFAULT_BLOCK_VARIANTS,
        _check_indices,
        _densify_window,
    )
    from spark_examples_tpu.ops.gramian import (
        gramian_accumulate_packed,
        pack_indicator_block,
    )

    width = block_variants or DEFAULT_BLOCK_VARIANTS
    g = jnp.zeros((n_samples, n_samples), dtype=accum_dtype)
    if device is not None:
        g = jax.device_put(g, device)
    windows = iter(windows)
    with obs.span("gramian.sparse.accumulate", n=n_samples):
        while True:
            # The dispatching thread's wait for its next window; the
            # last one finds the end of the stream.
            with obs.span("feed.wait"):
                window = next(windows, None)
            if window is None:
                break
            window_idx, lens = window
            lens = np.asarray(lens)
            _check_indices(np.asarray(window_idx), n_samples)
            route = window_route(lens, n_samples, density_threshold)
            nnz = int(lens.sum())
            with obs.span(
                "gramian.sparse.window",
                route=route,
                nnz=nnz,
                variants=int(lens.size),
            ):
                if route == "scatter":
                    g = sparse_gramian_accumulate(g, window_idx, lens)
                else:
                    dense_width = dense_panel_width(
                        int(lens.size), width
                    )
                    xp = pack_indicator_block(
                        _densify_window(
                            window_idx, lens, n_samples, dense_width
                        )
                    )
                    g = gramian_accumulate_packed(g, xp)
            _note_window(route, nnz)
    return g
