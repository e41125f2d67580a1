"""Gramian-free randomized sketch PCA: ``--pca-mode sketch``.

Every other PCA engine — fused, streamed dense, host-local sparse,
pod-sparse — materializes N×N tiles of G = XXᵀ, which at N = 10⁶ is
4 TB of f32: the footprint bound (:meth:`VariantsPcaDriver.
_sparse_device_g_bytes`) refuses long before the biobank north star.
The randomized-subspace literature (arxiv 1808.03374's genotype PCA,
Halko-Martinsson-Tropp) recovers the top-k eigenpairs of the centered
Gramian C = H·G·H (H the centering projector) from streamed products
alone. This module is that engine for the 0/1 indicator Gramian:

    C·Ω = Σ_w  H · X_w · (X_wᵀ · (H·Ω))

so each CSR carrier window contributes ``Y += X_w · (X_wᵀ · Ω̃)`` with
``Ω̃ = Ω − colmean(Ω)`` — two window-sized products, never an N×N tile
— and the left centering is one column-mean subtraction of the FINAL
panel (padding rows masked back to zero). The accumulation is a sum
over windows, so it is invariant to window arrival order: the
completion-order ingest pipeline and the pod protocol's per-step
gangs need no re-sorting (pinned by the shuffled-order goldens).

Window routing reuses the sparse engine's machinery wholesale
(:mod:`spark_examples_tpu.ops.sparse`): the density-route switch
(:func:`window_route`), the padded carrier matrix with OOB sentinel +
``mode="drop"`` scatter for sparse windows, and the pow2
``dense_panel_width`` densify + MXU matmul pair for dense windows.
Sparse-route cost is O(nnz·l) per window (l = k+p panel columns, from
:func:`spark_examples_tpu.ops.pcoa.randomized_panel_width` — the ONE
panel-width policy); memory is O(N·l) everywhere, never O(N²)
(:func:`sketch_host_bytes` is the documented bound, asserted by test).

The finish is the shifted Nyström eigensolve (Tropp et al.): with
Y = C·Ω and shift ν ≈ √n·eps_f32·‖Y‖_F,

    Y_ν = Y + ν·Ω;  Q·R = qr(Y_ν);  B = sym(Ωᵀ·Y_ν);  L = chol(B)
    U₁·Σ·Vᵀ = svd(R·L⁻ᵀ);  λ̂ = max(Σ² − ν, 0);  V̂ = Q·U₁

Meshless runs do the whole finish host-side in f64; mesh runs replace
the tall QR with the shard_map TSQR over the pod
(:func:`spark_examples_tpu.parallel.sharded.sketch_tsqr`) and keep
only the (k+p)×(k+p) core on the host. ``--sketch-power-iters q``
re-streams the windows q extra times with Ω ← orth(Y) between passes
(the classic accuracy knob); the default 0 keeps the one-streamed-pass
discipline of the cold-stream pipeline (arxiv 1302.4332).

Spectrum-tolerance contract (the PairHMM-style pinned bars, asserted
by tests/test_sketch.py against the exact path at small N):

- FULL-RANK REGIME — panel covers the whole space (l ≥ n, e.g.
  ``--sketch-oversample`` ≥ n−k): the Nyström reconstruction is exact
  up to floating-point roundoff. Top-k eigenvalues match the exact
  path within ``SKETCH_FULLRANK_RTOL`` relative; sign-normalized
  coordinates within ``SKETCH_FULLRANK_ATOL`` absolute per entry.
- TOP-K REGIME — l < n with ≥ 2 power iterations on a cohort whose
  spectrum has a clear gap past k: top-k eigenvalues within
  ``SKETCH_TOPK_RTOL`` relative; coordinates within
  ``SKETCH_TOPK_ATOL`` absolute per entry.

Runs are REPRODUCIBLE, not bit-identical to exact: Ω is seeded
(``--sketch-seed``, threaded from the CLI), so the same seed + same
topology reproduces the same coordinates bit-for-bit, while different
seeds agree only within the tolerance contract.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, Iterable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from spark_examples_tpu.ops.sparse import (
    DEFAULT_SPARSE_DENSITY_THRESHOLD,
    SCATTER_CHUNK_VARIANTS,
    _pad_rows_for_scan,
    dense_panel_width,
    padded_carrier_matrix,
    window_route,
)

__all__ = [
    "SKETCH_FULLRANK_ATOL",
    "SKETCH_FULLRANK_RTOL",
    "SKETCH_TOPK_ATOL",
    "SKETCH_TOPK_RTOL",
    "SketchPanel",
    "gaussian_test_matrix",
    "sketch_eig",
    "sketch_host_bytes",
    "sketch_panel_blockwise",
]

# Tolerance contract (module docstring has the regime definitions).
# Full-rank: the only error sources are f32 accumulation roundoff and
# the ν shift — both orders below these bars at test N (≤ 256).
SKETCH_FULLRANK_RTOL = 1e-3
SKETCH_FULLRANK_ATOL = 1e-3
# Top-k: randomized approximation error dominates; the bars hold for
# gapped spectra with ≥ 2 power iterations (the test fixtures).
SKETCH_TOPK_RTOL = 5e-2
SKETCH_TOPK_ATOL = 5e-2


def sketch_host_bytes(n: int, l: int) -> int:
    """The sketch engine's documented host-footprint bound: O(N·l)
    f32/f64 panels — Y (with its row-sums companion column), Ω, and the
    centered Ω̃ working copy — never O(N²). The bench scale-out leg
    emits this next to ``ru_maxrss`` provenance, and the footprint test
    asserts no single allocation on the sketch path exceeds it."""
    # y (l+1 cols, f32) + omega (f32) + centered copy (f32) + the f64
    # finish copies of y and omega.
    return 4 * n * (3 * (l + 1)) + 8 * n * (2 * l)


def gaussian_test_matrix(n: int, width: int, seed: int) -> np.ndarray:
    """Seeded (n, width) f32 Gaussian Ω — the CLI-threaded
    ``--sketch-seed`` makes every run reproducible, and every process
    of a pod derives the IDENTICAL matrix (the accumulation is a
    collective over replicated panels, so Ω divergence would be silent
    corruption)."""
    rng = np.random.default_rng(seed)
    return rng.standard_normal((n, width)).astype(np.float32)


@dataclasses.dataclass
class SketchPanel:
    """The sketch ingest product — what ``--pca-mode sketch`` returns
    from ``ingest_gramian`` in place of an (N, N) Gramian.

    ``y`` is the centered sketch C·Ω_final and ``omega`` the FINAL test
    matrix (orth(Y) after power iterations, Ω̃ otherwise) — host f64
    arrays always; mesh runs (``mesh`` set, routing the finish through
    the pod TSQR) carry n_padded rows with zeroed padding. ``row_sums``
    carries G's row sums — accumulated by a ones companion column on
    the first pass — so the non-zero-rows parity print survives
    without G."""

    y: Any
    omega: Any
    row_sums: np.ndarray
    n: int
    k: int
    l: int
    seed: int
    power_iters: int
    mesh: Any = None
    host_peak_bytes: int = 0


def _note_sketch_window(route: str, count: int = 1) -> None:
    """Per-window sketch telemetry (one registration site per metric,
    GL003; the label set is enforced by
    ``validate_trace._LABELED_COUNTERS``). ``count`` follows the pod
    protocol's coalesced gangs exactly as the sparse engine's counter
    does."""
    from spark_examples_tpu import obs

    obs.get_registry().counter(
        "sketch_windows_total",
        "CSR windows applied to the randomized sketch panel",
    ).labels(route=route).inc(count)


@partial(jax.jit, donate_argnums=(0,))
def _sketch_scatter_update(y: Any, omega: Any, idx: Any) -> Any:
    """One sparse-route window into the panel: ``Y += X·(Xᵀ·Ω̃)``
    without forming X. ``idx`` is the padded carrier matrix
    ``(V_pad, k_bucket)`` (V_pad a multiple of the scan chunk,
    sentinel = y rows, so padded entries gather zero rows and their
    scatter drops). Per variant v the update adds
    ``t_v = Σ_{a} Ω̃[idx[v, a]]`` back to every carrier row — the
    scan bounds the transient at ``chunk · k_bucket · l``."""
    shape = (
        idx.shape[0] // SCATTER_CHUNK_VARIANTS,
        SCATTER_CHUNK_VARIANTS,
        idx.shape[1],
    )

    def body(acc: Any, ci: Any) -> Tuple[Any, None]:
        rows = omega.at[ci].get(mode="fill", fill_value=0)
        t = jnp.sum(rows, axis=1)
        upd = jnp.broadcast_to(t[:, None, :], rows.shape)
        return acc.at[ci].add(upd, mode="drop"), None

    y, _ = jax.lax.scan(body, y, idx.reshape(shape))
    return y


@partial(jax.jit, donate_argnums=(0,))
def _sketch_dense_update(y: Any, omega: Any, xp: Any) -> Any:
    """One dense-route window: unpack the bit-packed indicator panel
    (the same pow2-bucketed packed bytes the Gramian MXU path ships)
    and ride two MXU matmuls — ``Y += X·(Xᵀ·Ω̃)``."""
    from spark_examples_tpu.ops.gramian import unpack_indicator_block

    xb = unpack_indicator_block(xp, 8 * xp.shape[1]).astype(y.dtype)
    return y + xb @ (xb.T @ omega)


def _center_columns(
    panel: np.ndarray, n: int
) -> np.ndarray:
    """Subtract per-column means over the n REAL rows; rows past n
    (mesh padding) are zeroed back (C's padded block is zero, so the
    centered sketch must vanish there too)."""
    out = panel - panel[:n].mean(axis=0, keepdims=True)
    out[n:] = 0.0
    return out


def _augmented_omega(
    omega: np.ndarray, n: int, first_pass: bool
) -> np.ndarray:
    """The streamed right-hand panel: centered Ω̃ plus one companion
    column — all-ones on the first pass (its accumulation is
    ``X·(Xᵀ·1)`` = G's row sums, the parity-print vector), zeros on
    power-iteration re-passes (inert, but keeps the per-window
    executable geometry identical across passes — no retrace)."""
    aug = np.zeros((omega.shape[0], omega.shape[1] + 1), omega.dtype)
    aug[:, :-1] = _center_columns(omega, n)
    if first_pass:
        aug[:n, -1] = 1.0
    return aug


def sketch_panel_blockwise(
    windows_factory: Callable[[], Iterable[Tuple[np.ndarray, np.ndarray]]],
    n_samples: int,
    k: int,
    oversample: Optional[int] = None,
    power_iters: Optional[int] = None,
    seed: int = 0,
    density_threshold: float = DEFAULT_SPARSE_DENSITY_THRESHOLD,
    block_variants: Optional[int] = None,
) -> SketchPanel:
    """Stream CSR carrier windows into a single-device (N, k+p) sketch
    panel — the meshless sketch engine (mesh runs go through
    :func:`spark_examples_tpu.parallel.sharded.sharded_sketch_panel`).

    ``windows_factory`` returns a FRESH window iterator per call —
    power iterations re-stream the cohort once per extra pass. Routing,
    padding, and bucketing reuse the sparse engine's helpers verbatim,
    so the per-window executable census stays O(log) by the same
    bucket arguments (GL012).
    """
    from spark_examples_tpu import obs
    from spark_examples_tpu.arrays.blocks import (
        DEFAULT_BLOCK_VARIANTS,
        _check_indices,
        _densify_window,
    )
    from spark_examples_tpu.ops.gramian import pack_indicator_block
    from spark_examples_tpu.ops.pcoa import (
        DEFAULT_SKETCH_POWER_ITERS,
        randomized_panel_width,
    )

    if oversample is None:
        oversample = _default_sketch_oversample()
    if power_iters is None:
        power_iters = DEFAULT_SKETCH_POWER_ITERS
    width = block_variants or DEFAULT_BLOCK_VARIANTS
    l = randomized_panel_width(n_samples, k, oversample)
    omega0 = gaussian_test_matrix(n_samples, l, seed)
    omega_cur = omega0
    row_sums = np.zeros(n_samples, dtype=np.float64)
    y_host: Optional[np.ndarray] = None
    for p in range(power_iters + 1):
        first = p == 0
        aug = _augmented_omega(omega_cur, n_samples, first_pass=first)
        om_dev = jnp.asarray(aug)
        y = jnp.zeros((n_samples, l + 1), dtype=jnp.float32)
        with obs.span(
            "gramian.sketch.accumulate",
            n=n_samples,
            l=l,
            sketch_pass=p,
        ):
            for window_idx, lens in windows_factory():
                lens = np.asarray(lens)
                _check_indices(np.asarray(window_idx), n_samples)
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
                            sentinel=n_samples,
                            n_rows=_pad_rows_for_scan(lens.size),
                        )
                        y = _sketch_scatter_update(
                            y, om_dev, jnp.asarray(idx)
                        )
                    else:
                        xp = pack_indicator_block(
                            _densify_window(
                                window_idx,
                                lens,
                                n_samples,
                                dense_panel_width(
                                    int(lens.size), width
                                ),
                            )
                        )
                        y = _sketch_dense_update(
                            y, om_dev, jnp.asarray(xp)
                        )
                _note_sketch_window(route)
        y_np = np.asarray(y, dtype=np.float64)
        y_np = _merge_partial_panels(y_np)
        if first:
            row_sums = y_np[:, -1].copy()
        y_host = _center_columns(y_np[:, :-1], n_samples)
        if p < power_iters:
            # Ω ← orth(Y): the next pass streams against an
            # orthonormal (re-centered) basis of the current range.
            q, _ = np.linalg.qr(y_host)
            omega_cur = q.astype(np.float32)
    omega_final = (
        _center_columns(
            omega_cur.astype(np.float64), n_samples
        )
        if power_iters
        else _center_columns(
            omega0.astype(np.float64), n_samples
        )
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
        host_peak_bytes=sketch_host_bytes(n_samples, l),
    )


def _default_sketch_oversample() -> int:
    from spark_examples_tpu.ops.pcoa import DEFAULT_RANDOMIZED_OVERSAMPLE

    return DEFAULT_RANDOMIZED_OVERSAMPLE


def _merge_partial_panels(y_np: np.ndarray) -> np.ndarray:
    """Multi-controller runs whose panel is NOT collectively
    accumulated (meshless, or a host-local mesh fed per-host manifest
    slices) hold per-host partial sums — merge over DCN. The
    process-spanning pod accumulator never calls this (its every step
    was already a collective over the full window set)."""
    if jax.process_count() == 1:
        return y_np
    from spark_examples_tpu.parallel.distributed import (
        allreduce_gramian,
    )

    return np.asarray(allreduce_gramian(y_np))


def _nystrom_core(
    r: np.ndarray, b: np.ndarray, nu: float
) -> Tuple[np.ndarray, np.ndarray]:
    """The (k+p)×(k+p) host-f64 core shared by the meshless and TSQR
    finishes: B's Cholesky whitening, the small SVD, and the shift
    removal. Returns ``(u1, vals)`` with ``vals`` descending."""
    b = (b + b.T) / 2.0
    jitter = 0.0
    eye = np.eye(b.shape[0])
    for attempt in range(4):
        try:
            chol = np.linalg.cholesky(b + jitter * eye)
            break
        except np.linalg.LinAlgError:
            base = max(np.trace(b) / b.shape[0], nu, 1e-30)
            jitter = base * (1e-12 * 10 ** (2 * attempt))
    else:
        raise np.linalg.LinAlgError(
            "sketch core matrix B = sym(Omega^T Y_nu) is not positive "
            "definite after jitter retries — the sketch panel is "
            "numerically degenerate (all-zero cohort windows?)"
        )
    # m = R·L⁻ᵀ via one triangular solve: L·Z = Rᵀ ⇒ m = Zᵀ.
    m = np.linalg.solve(chol, r.T).T
    u1, s, _ = np.linalg.svd(m)
    vals = np.maximum(s * s - nu, 0.0)
    return u1, vals


def sketch_eig(
    panel: SketchPanel, k: int, timer: Any = None
) -> Tuple[np.ndarray, np.ndarray]:
    """Top-k eigenpairs of the centered Gramian from a sketch panel.

    Returns ``(coords, vals)``: coords (n, k) sign-normalized unit
    eigenvector entries — the same surface the exact finishes emit —
    and the k approximate eigenvalues. The spectral-gap check runs on
    the full l-wide Ritz spectrum (l ≥ k+1 by the panel-width floor),
    exactly like every exact tier."""
    from spark_examples_tpu import obs
    from spark_examples_tpu.ops.pcoa import (
        check_spectral_gap,
        normalize_eigvec_signs,
    )

    with obs.span("gramian.sketch.finish", n=panel.n, k=k, l=panel.l):
        if panel.mesh is not None:
            from spark_examples_tpu.parallel.sharded import (
                sharded_sketch_finish,
            )

            coords, vals = sharded_sketch_finish(panel, k)
        else:
            y, omega = panel.y, panel.omega
            norm = float(np.linalg.norm(y))
            if norm == 0.0:
                # All-zero cohort: C = 0, every coordinate is 0.
                return (
                    np.zeros((panel.n, k)),
                    np.zeros(k),
                )
            nu = np.sqrt(panel.n) * np.finfo(np.float32).eps * norm
            y_nu = y + nu * omega
            q, r = np.linalg.qr(y_nu)
            b = omega.T @ y_nu
            u1, vals = _nystrom_core(r, b, nu)
            coords = q @ u1
        check_spectral_gap(vals, k, timer=timer)
        coords = normalize_eigvec_signs(
            np.asarray(coords)[: panel.n, :k]
        )
        return coords, np.asarray(vals)[:k]
