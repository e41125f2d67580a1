"""Fused PCoA: streamed packed accumulation + a single-dispatch finish.

Why this exists: device-side compute for the whole north-star workload
(Gramian + centering + top-k eig at N=2504, V=65536) is small next to the
host→device transfer of the cohort and the synchronous host-visible
results, so the shape of the computation is chosen to move the fewest
bytes and pay the fewest syncs:

    bit-packed transfer        (the irreducible bytes, 8× fewer than int8)
    overlapped with host pack  (np.packbits runs in the prefetch thread
                               while the previous chunk is in flight)
    async accumulate dispatches (G += unpack(chunk) @ unpack(chunk).T,
                               donated in place in HBM — enqueue is
                               non-blocking, so dispatches hide entirely
                               under the transfer stream)
    ONE finish dispatch        (center → CholeskyQR subspace eig → row
                               sums, all on device)
    ONE packed readback        (coords, eigenvalues, row sums in a single
                               (N, p+4) f32 array — one sync, not four)

Chunks stream through :func:`spark_examples_tpu.arrays.feed.device_prefetch`
— the same double-buffered feed the blockwise product path uses — so pack
and transfer overlap and the only serial terms left are the link itself
and one sync. It is also the SHIPPED path: ``VariantsPcaDriver`` routes
single-host unsharded runs through :func:`fused_finish` (``--pca-mode``),
and ``bench.py``'s ``fused`` mode calls :func:`pcoa_fused_blocks`, the
exact composition the CLI executes.

The top-k eigendecomposition inside the finish program is randomized
subspace iteration with **CholeskyQR** panel orthonormalization: ``qr`` on
TPU lowers to sequential Householder steps (measured 2.4× slower
end-to-end), whereas CholeskyQR is two MXU matmuls plus a (p, p) Cholesky
+ triangular solve — numerically fine here because panels are
re-orthonormalized every iteration and PCoA spectra are mild (κ(panel
Gram) ≈ (λ₁/λ_p)² per sweep; the f32 limit ~2^12 dwarfs realistic
population-structure ratios). Convergence is *checked*, not assumed: the
finish program computes the top-k Ritz residuals ``‖C·v − λ·v‖/|λ|`` from
its own final matmul and :func:`fused_finish` raises them as a loud
:class:`EigResidualWarning` when they exceed the parity bar's scale.

Semantics match :func:`spark_examples_tpu.ops.pcoa.pcoa` exactly: raw
sign-normalized eigenvectors of the double-centered Gramian ordered by
|λ| descending (the MLlib composition equivalence — pcoa.py module
docstring; reference ``VariantsPca.scala:198-231``). Accuracy vs dense
``eigh`` is set by ``iters``; the defaults land ≤1e-4 max coordinate error
on structured (population-structure) cohorts and are verified against the
f64 MLlib-literal golden in tests and in ``bench.py``. The spectral-gap
degeneracy check runs host-side on the returned Ritz values, exactly as
the dense path's (:func:`~spark_examples_tpu.ops.pcoa.check_spectral_gap`).
"""

from __future__ import annotations

import warnings
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from spark_examples_tpu.ops.centering import double_center
from spark_examples_tpu.ops.gramian import gramian_blockwise
from spark_examples_tpu.ops.pcoa import (
    check_spectral_gap,
    normalize_eigvec_signs,
)

__all__ = [
    "EigResidualWarning",
    "RankDeficientGramianError",
    "panel_conditioning",
    "fused_finish",
    "fused_forward",
    "pcoa_fused_blocks",
    "pcoa_fused_packed",
    "subspace_eig_cholqr",
]

# The shipped sweep defaults — shared by fused_finish and fused_forward
# so the driver contract (__graft_entry__) certifies exactly the
# composition --pca-mode auto runs; changing one changes both.
_DEF_OVERSAMPLE = 8
_DEF_ITERS = 40


class EigResidualWarning(UserWarning):
    """Subspace iteration left a top-k Ritz residual above the bar."""


class RankDeficientGramianError(ValueError):
    """The centered Gramian has numerical rank below the panel width.

    CholeskyQR factors the panel Gram ``YᵀY``, whose condition number is
    κ(Y)²: once σ_min(Y)/σ_max(Y) falls under √eps the factorization is
    meaningless, and whether it then yields NaN or finite garbage depends
    on the runtime. :func:`fused_finish` raises this BEFORE reading any
    eigenpair, from an explicit measurement of the first panel.
    """


# CholeskyQR's breakdown point in f32: κ(Y)² · eps ≈ 1.
_PANEL_COND_FLOOR = float(np.sqrt(np.finfo(np.float32).eps))


def panel_conditioning(c, p: int, key):
    """σ_r(Y₀)/σ_1(Y₀) for the first subspace panel ``Y₀ = C·Q₀`` —
    jittable.

    ``r = min(p, N−1)``: double centering always leaves the all-ones
    null vector, so a cohort with N ≤ p can fill at most N−1 panel
    directions and is not degenerate for that alone. Householder QR then
    an SVD of the (p, p) factor measure the singular values to
    eps·‖Y₀‖ (squaring into ``YᵀY`` first would bury the answer at the
    √eps noise floor it is compared against). ``Q₀`` is drawn exactly as
    :func:`subspace_eig_cholqr` draws it. An all-zero C gives NaN, which
    the host check treats as degenerate.
    """
    n = c.shape[0]
    r = min(p, n - 1)
    if r < 1:
        return jnp.asarray(1.0, c.dtype)
    q = jax.random.normal(key, (n, p), c.dtype)
    with jax.default_matmul_precision("float32"):
        y = c @ q
    sv = jnp.linalg.svd(jnp.linalg.qr(y, mode="r"), compute_uv=False)
    return sv[r - 1] / sv[0]


def subspace_eig_cholqr(c, k: int, oversample: int = 8, iters: int = 16,
                        key=None):
    """Top-|λ| eigenpairs of symmetric ``c`` — jittable, MXU-only inner loop.

    Returns ``(vecs (N, p), vals (p,), resid ())`` with ``p = k+oversample``,
    |λ|-ordered and sign-normalized; ``resid`` is the max top-k relative
    Ritz residual ``‖C·v − λ·v‖/|λ|`` computed from the final products
    (no extra O(N²) work). Callers slice to k after the host-side checks.
    """
    n = c.shape[0]
    p = min(n, k + oversample)
    if key is None:
        key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (n, p), c.dtype)
    eye = jnp.eye(p, dtype=c.dtype)

    # TPU matmuls default to bf16 MXU passes — fine for the int8-exact
    # Gramian, fatal for eigenvector refinement (the iteration stalls at
    # ~1e-4 instead of converging to ~3e-7, measured on chip round 4).
    # Panel matmuls are O(N²p) — forcing f32-equivalent precision costs
    # ~3× on a term that is ~1% of the phase.
    with jax.default_matmul_precision("float32"):

        def body(q, _):
            y = c @ q
            # CholeskyQR: orthonormalize through the (p, p) Gram factor.
            # The jitter is SCALE-RELATIVE (eps · mean column norm², per
            # advisor round 4: an absolute finfo.tiny only guards
            # exactly-zero columns) plus a tiny absolute floor for the
            # all-zero-C edge; near-rank-deficient panels stay
            # factorizable and the discarded directions are dropped by
            # the |λ| ordering anyway.
            yty = y.T @ y
            jitter = (
                jnp.finfo(c.dtype).eps * (jnp.trace(yty) / p)
                + jnp.finfo(c.dtype).tiny
            )
            r = jnp.linalg.cholesky(yty + jitter * eye)
            q = jax.lax.linalg.triangular_solve(
                r, y, left_side=False, lower=True, transpose_a=True
            )
            return q, None

        q, _ = jax.lax.scan(body, q, None, length=iters)
        y = c @ q
        b = q.T @ y
        w, u = jnp.linalg.eigh(b)
        order = jnp.argsort(-jnp.abs(w))
        vecs = q @ u[:, order]
        vals = w[order]
        # Top-k Ritz residuals from the products already in hand:
        # C·v = (C·q)·u = y·u, so ‖C·v − λ·v‖ needs no new O(N²) matmul.
        uk, wk = u[:, order[:k]], vals[:k]
        rk = y @ uk - (q @ uk) * wk
        resid = jnp.max(
            jnp.linalg.norm(rk, axis=0)
            / jnp.maximum(jnp.abs(wk), jnp.finfo(c.dtype).tiny)
        )
        return normalize_eigvec_signs(vecs), vals, resid


@partial(jax.jit, static_argnames=("k", "oversample", "iters"))
def _finish_jit(g, k, oversample, iters, key):
    """Center → subspace eig → row sums, packed into ONE output array.

    One (N, p+4) f32 readback instead of four separate device→host
    reads: one sync, not four. Layout: ``[:, :p]`` eigenvectors,
    ``[:, p]`` row sums of G (the "Non zero rows" parity print,
    ``VariantsPca.scala:207-208``), ``[:p, p+1]`` eigenvalues,
    ``[0, p+2]`` max top-k relative Ritz residual, ``[0, p+3]`` the
    first panel's conditioning (:func:`panel_conditioning`). ``n ≥ p``
    always (``p = min(n, k+oversample)``), so the value rows exist.
    """
    gf = g.astype(jnp.float32)
    row_sums = jnp.sum(gf, axis=1)
    c = double_center(gf)
    vecs, vals, resid = subspace_eig_cholqr(
        c, k, oversample=oversample, iters=iters, key=key
    )
    n, p = vecs.shape
    out = jnp.zeros((n, p + 4), jnp.float32)
    out = out.at[:, :p].set(vecs)
    out = out.at[:, p].set(row_sums)
    out = out.at[:p, p + 1].set(vals)
    out = out.at[0, p + 2].set(resid)
    out = out.at[0, p + 3].set(panel_conditioning(c, p, key))
    return out


def fused_finish(
    g,
    k: int,
    oversample: int = _DEF_OVERSAMPLE,
    iters: int = _DEF_ITERS,
    seed: int = 0,
    timer=None,
    resid_warn: float = 1e-3,
    max_retries: int = 1,
):
    """(N, N) Gramian → top-k principal coordinates in ONE dispatch.

    The finish half of the fused path — the piece ``VariantsPcaDriver``
    runs after the streamed packed accumulation (``--pca-mode auto`` /
    ``fused``). One jit (centering + CholeskyQR subspace eig + row sums),
    one packed host readback. Same coordinate semantics as
    ``pcoa(g, k)``; convergence and spectral-gap degeneracy are checked
    host-side on the returned values.

    ``resid_warn`` is a CONVERGENCE TARGET, not just a warning bar (the
    driver threads ``--eig-tol`` into it): when the max top-k relative
    Ritz residual exceeds it, the sweep re-runs with doubled iterations
    up to ``max_retries`` times (G is still device-resident, so a retry
    is one more dispatch — rare, and only marginal-spectrum cohorts pay
    it) before warning loudly. Eigenvector error is O(resid / gap).

    Returns ``(coords (N, k), vals (k,) float64, row_sums (N,))``.
    """
    from spark_examples_tpu import obs

    n = int(g.shape[0])
    p = min(n, k + oversample)
    gd = jnp.asarray(g)
    for attempt in range(max_retries + 1):
        run_iters = iters << attempt
        key = jax.random.PRNGKey(seed)
        with obs.span(
            "fused_finish", n=n, k=k, iters=run_iters, attempt=attempt
        ):
            out = np.asarray(_finish_jit(gd, k, oversample, run_iters, key))
        cond = float(out[0, p + 3])
        if not cond >= _PANEL_COND_FLOOR:  # NaN (all-zero C) included
            raise RankDeficientGramianError(
                "the centered Gramian is rank-deficient for the fused "
                f"finish: first-panel σ_{min(p, n - 1)}/σ_1 = {cond:.3g} "
                f"< √eps = {_PANEL_COND_FLOOR:.3g} (CholeskyQR needs a "
                f"rank-{min(p, n - 1)} panel); rerun with --pca-mode "
                "stream (dense eigh) or --precise"
            )
        resid = float(out[0, p + 2])
        if not np.isfinite(resid):
            # Panel collapse is deterministic for a given (G, seed):
            # retrying with doubled iterations recompiles and re-runs a
            # dispatch guaranteed to produce the same NaN. Fall straight
            # through to the non-finite raise below.
            break
        if resid <= resid_warn:
            break
        if attempt < max_retries:
            if timer is not None:
                timer.note(
                    f"fused eig residual {resid:.2e} > {resid_warn:g} "
                    f"after {run_iters} iterations — retrying doubled"
                )
    vecs = out[:, :p]
    row_sums = out[:, p]
    vals = out[:p, p + 1].astype(np.float64)
    if not np.isfinite(vals).all() or not np.isfinite(resid):
        # The rank check above passed, so a NaN here is a fault, not a
        # degenerate cohort: it must never flow silently into the gap
        # check and out through emit_result as all-NaN coordinates.
        raise FloatingPointError(
            "fused eigendecomposition produced non-finite Ritz values "
            f"(vals={vals[: k + 1]}, resid={resid}) on a panel that "
            "passed the rank check"
        )
    if timer is not None:
        timer.note(
            f"fused eig residual {resid:.2e} ({run_iters} iterations)"
        )
    if resid > resid_warn:
        warnings.warn(
            f"fused subspace iteration residual {resid:.2e} exceeds "
            f"{resid_warn:g} after {run_iters} iterations — coordinates "
            "may not have converged to dense-eigh accuracy on this "
            "cohort; use --pca-mode stream (dense eigh) or --precise to "
            "cross-check",
            EigResidualWarning,
            stacklevel=2,
        )
    check_spectral_gap(vals, k, timer=timer)
    return vecs[:, :k], vals[:k], row_sums


def fused_forward(x, k: int = 2):
    """The shipped flagship composition as ONE jittable function.

    int8 0/1 indicators → integer-MXU Gramian → fused finish (centering
    + CholeskyQR subspace eig) → (N, k) coordinates, with the SAME sweep
    defaults ``--pca-mode auto`` ships — the driver contract
    (``__graft_entry__.entry``) compiles exactly this, so the certified
    path and the product path cannot drift.
    """
    from spark_examples_tpu.ops.gramian import mxu_cross_product

    out = _finish_jit(
        mxu_cross_product(x, jnp.float32, jnp.int8),
        k,
        _DEF_OVERSAMPLE,
        _DEF_ITERS,
        jax.random.PRNGKey(0),
    )
    return out[:, :k]


def pcoa_fused_blocks(
    blocks,
    n_samples: int,
    k: int,
    oversample: int = _DEF_OVERSAMPLE,
    iters: int = _DEF_ITERS,
    seed: int = 0,
    compute_dtype=None,
    device=None,
    timer=None,
):
    """0/1 indicator blocks → top-k principal coordinates, fully fused.

    THE shipped fast path (and ``bench.py``'s ``fused`` mode): the blocks
    stream through the bit-packed double-buffered accumulator
    (:func:`~spark_examples_tpu.ops.gramian.gramian_blockwise` with
    ``packed=True`` — pack, transfer, and matmul overlap; G accumulates
    donated in HBM), then :func:`fused_finish` runs centering + subspace
    eig + row sums in one dispatch with one packed readback. The variant
    axis is unbounded (HBM holds G plus one block transient, never the
    cohort), which is what lets the same program run at all-autosomes V.

    Returns ``(coords (N, k), vals (k,), row_sums (N,))``.
    """
    g = gramian_blockwise(
        blocks,
        n_samples,
        packed=True,
        compute_dtype=compute_dtype,
        device=device,
    )
    return fused_finish(
        g, k, oversample=oversample, iters=iters, seed=seed, timer=timer
    )


def pcoa_fused_packed(
    x_packed: np.ndarray,
    n_bits: int,
    k: int,
    chunk_bits: int = 65536,
    oversample: int = _DEF_OVERSAMPLE,
    iters: int = _DEF_ITERS,
    seed: int = 0,
    compute_dtype=None,
    device=None,
    timer=None,
):
    """Packed indicator matrix → top-k principal coordinates.

    Whole-cohort API over an already-packed ``(N, ⌈V/8⌉)`` uint8 matrix
    (:func:`pack_indicator_block` output): the packed variant axis is cut
    into ``chunk_bits``-wide pieces which stream through the
    double-buffered feed into donated accumulate dispatches — transfer of
    chunk i+1 overlaps chunk i's matmul — then one
    :func:`fused_finish` dispatch. Prefer :func:`pcoa_fused_blocks` when
    the cohort is still in unpacked blocks (it overlaps the host-side
    pack as well); this entry point serves callers that keep a packed
    cohort resident (tests, re-analysis at different k).

    Args:
      x_packed: ``(N, ⌈V/8⌉)`` uint8 packed 0/1 indicators, whole cohort.
      n_bits: V — the true variant count (pad bits beyond it are zero and
        inert in the Gramian).
      chunk_bits: variant bits per accumulate dispatch; bounds the
        unpacked (N, chunk_bits) int8 HBM transient and sets the
        transfer/compute overlap granularity.

    Returns:
      ``(coords (N, k) np.ndarray, vals (k,) np.ndarray)`` — same
      semantics as ``pcoa(gramian(X), k)``.
    """
    x_packed = np.asarray(x_packed)
    chunk_bits = int(min(chunk_bits, max(8, n_bits)))
    chunk_bits = ((chunk_bits + 7) // 8) * 8
    chunk_bytes = chunk_bits // 8

    def chunks():
        for off in range(0, x_packed.shape[1], chunk_bytes):
            piece = x_packed[:, off : off + chunk_bytes]
            if piece.shape[1] != chunk_bytes:
                # Zero bytes unpack to zero columns — inert in X @ X.T —
                # and keep every accumulate step on one compiled shape.
                piece = np.pad(
                    piece, ((0, 0), (0, chunk_bytes - piece.shape[1]))
                )
            yield piece

    g = gramian_blockwise(
        chunks(),
        x_packed.shape[0],
        compute_dtype=compute_dtype,
        device=device,
        packed=True,
        prepacked=True,
    )
    coords, vals, _ = fused_finish(
        g, k, oversample=oversample, iters=iters, seed=seed, timer=timer
    )
    return coords, vals
