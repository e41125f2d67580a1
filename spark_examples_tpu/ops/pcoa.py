"""Principal-coordinate analysis: eigendecomposition of the centered Gramian.

Reference pipeline (``VariantsPca.scala:224-231``): the double-centered rows
are wrapped in an MLlib ``RowMatrix`` and ``computePrincipalComponents(k)``
runs — which (a) forms the *covariance matrix of the rows* and (b)
eigendecomposes it on the driver via Breeze/LAPACK, returning the top-k
eigenvectors as an N×k matrix whose row i is emitted as sample i's
coordinates (``VariantsPca.scala:227-230``).

Equivalence used here: the double-centered matrix C is symmetric with
exactly-zero column means, so the covariance of its rows is
``cov = CᵀC/(n−1) = C²/(n−1)``. C² shares eigenvectors with C and squares
the eigenvalues, so MLlib's principal components are exactly the
eigenvectors of C ordered by **|λ| descending** — one ``eigh`` of C instead
of forming C². ``mllib_principal_components_reference`` implements MLlib's
literal composition in numpy f64 and is the golden the fast path is tested
against (the BASELINE 1e-4 parity bar, modulo eigenvector sign which is
arbitrary in any LAPACK-family solver and normalized deterministically here).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from spark_examples_tpu.ops.centering import double_center

__all__ = [
    "DEFAULT_RANDOMIZED_OVERSAMPLE",
    "DEFAULT_SKETCH_POWER_ITERS",
    "SpectralGapWarning",
    "check_spectral_gap",
    "randomized_panel_width",
    "topk_with_gap_check",
    "pcoa",
    "principal_components",
    "mllib_principal_components_reference",
    "normalize_eigvec_signs",
]

# The ONE oversampling default every randomized top-k consumer derives
# from: the exact sharded finish (parallel.sharded.topk_eig_randomized)
# and the Gramian-free sketch engine (ops/sketch.py, --sketch-oversample)
# both resolve their panel width through randomized_panel_width with
# this value — a drifted copy in either caller would silently change
# which Ritz pairs exist for the gap check.
DEFAULT_RANDOMIZED_OVERSAMPLE = 8

# Extra full streamed passes the sketch engine runs with Ω ← orth(Y)
# between them (--sketch-power-iters). 0 = ONE pass over the windows —
# the cold-stream overlap discipline (arxiv 1302.4332); the tolerance
# goldens use ≥ 2 where the approximation regime needs them.
DEFAULT_SKETCH_POWER_ITERS = 0


def randomized_panel_width(
    n: int, k: int, oversample: int = DEFAULT_RANDOMIZED_OVERSAMPLE
) -> int:
    """Panel width p for a randomized top-k eigensolve — the ONE place
    the k+1-values calling convention lives.

    Every consumer of randomized subspace iteration
    (:func:`spark_examples_tpu.parallel.sharded.topk_eig_randomized`,
    and through it the sharded finish — and the Gramian-free sketch
    engine of :mod:`spark_examples_tpu.ops.sketch`, whose Ω panel and
    Nyström core are sized by exactly this width) needs the
    oversampled panel to
    carry AT LEAST ``min(k+1, n)`` Ritz pairs: ``k`` for the returned
    components plus one past the gap for :func:`check_spectral_gap`
    (which silently returns when no value past index k−1 exists — the
    silent-skip this helper exists to make impossible). Before this
    helper the ``k + oversample`` arithmetic was duplicated implicitly
    at call sites, so an ``oversample=0`` caller would both shrink the
    subspace below the convention AND disable the degeneracy warning
    without a trace. Centralized: ``min(n, k + max(oversample, 1))`` —
    the floor guarantees the k+1-th value whenever the spectrum has one
    (n > k), and the returned width is what callers must allocate and
    slice against (``vecs[:, :k]``/``vals[:k]`` can then never drop a
    requested component).
    """
    if k < 1:
        raise ValueError(f"top-k eigensolve needs k >= 1, got {k}")
    return min(n, k + max(int(oversample), 1))


class SpectralGapWarning(UserWarning):
    """Top-k eigenvalue gap is near-degenerate; coordinates are unstable."""


def check_spectral_gap(vals, k: int, warn_ratio: float = 0.95, timer=None):
    """Warn loudly when the k-th eigen-gap is near-degenerate.

    ``vals`` are |λ|-ordered eigen/Ritz values with at least one entry past
    index k−1 (callers request k+1 values; the randomized path's
    oversampled panel has them anyway). A ratio |λ_{k+1}|/|λ_k| near 1
    means the top-k eigenbasis is rotation-ambiguous — for dense ``eigh``
    exactly as for randomized iteration: a weakly structured cohort has no
    well-defined PC2, and that must be loud, not silent (round-2 verdict
    weak #5). The ratio also lands in the stage-timer report when a
    ``timer`` (utils.tracing.StageTimer) is passed.
    """
    import warnings

    if len(vals) <= k:
        return  # caller could not supply a value past the gap
    lam_k, lam_next = abs(float(vals[k - 1])), abs(float(vals[k]))
    if lam_k == 0.0:
        return  # rank-deficient below k: coordinates there are zeros
    ratio = lam_next / lam_k
    if timer is not None:
        timer.note(f"spectral gap |λ{k + 1}|/|λ{k}| = {ratio:.4f}")
    if ratio > warn_ratio:
        warnings.warn(
            f"near-degenerate spectral gap: |λ{k + 1}|/|λ{k}| = {ratio:.4f}"
            f" > {warn_ratio}. The top-{k} eigenbasis is rotation-ambiguous"
            " (for dense eigh too) — principal coordinates beyond the"
            " well-separated eigenvalues are unstable on this cohort.",
            SpectralGapWarning,
            stacklevel=3,
        )


def topk_with_gap_check(eig_fn, k, n, timer=None, vals_are_squared=False):
    """Request k+1 eigenpairs, gap-check past k, slice back to k.

    The one place holding the pattern every dense eig call site needs:
    the ``min(k+1, n)`` clamp, passing the UNsliced values to
    :func:`check_spectral_gap`, then trimming coords/vals to k.
    ``eig_fn(kk)`` returns ``(coords (n, kk), vals (kk,))`` ordered by
    magnitude descending. ``vals_are_squared``: MLlib-literal covariance
    eigenvalues are λ(C)²/(n−1), so their ratio is the square of the
    centered-Gramian gap ratio every other tier checks — take the sqrt
    first so the 0.95 threshold means the same cohort everywhere.
    """
    coords, vals = eig_fn(min(k + 1, n))
    v = np.abs(np.asarray(vals, dtype=np.float64))
    if vals_are_squared:
        v = np.sqrt(v)
    check_spectral_gap(v, k, timer=timer)
    return coords[:, :k], vals[:k]


def normalize_eigvec_signs(vecs):
    """Deterministic sign convention: largest-|entry| of each column > 0.

    Eigenvector signs are arbitrary; LAPACK/Breeze/XLA may disagree. Fixing
    the sign so the largest-magnitude component of each column is positive
    (ties broken by lowest row index via argmax) makes output stable across
    backends and is the convention the parity tests compare under.
    """
    if isinstance(vecs, np.ndarray):
        idx = np.argmax(np.abs(vecs), axis=0)
        signs = np.sign(vecs[idx, np.arange(vecs.shape[1])])
        signs = np.where(signs == 0, 1.0, signs)
        return vecs * signs
    idx = jnp.argmax(jnp.abs(vecs), axis=0)
    signs = jnp.sign(vecs[idx, jnp.arange(vecs.shape[1])])
    signs = jnp.where(signs == 0, 1.0, signs)
    return vecs * signs


@partial(jax.jit, static_argnames=("k",))
def principal_components(c, k):
    """Top-k principal components of a double-centered symmetric matrix.

    Returns ``(coords, eigvals)``: ``coords`` is N×k (row i = sample i's
    coordinates, matching the reference's use of the MLlib PC matrix rows),
    ``eigvals`` the corresponding eigenvalues of C (note: MLlib's reported
    eigenvalues would be these squared over n−1; the *vectors* are what the
    reference emits). Ordered by |λ| descending, signs normalized.
    """
    w, v = jnp.linalg.eigh(c)
    order = jnp.argsort(-jnp.abs(w))[:k]
    vecs = normalize_eigvec_signs(v[:, order])
    return vecs, w[order]


@partial(jax.jit, static_argnames=("k", "scale"))
def _pcoa_jit(g, k, scale):
    c = double_center(g)
    coords, w = principal_components(c, k)
    if scale:
        coords = coords * jnp.sqrt(jnp.maximum(w, 0.0))
    return coords, w


def pcoa(g, k, scale=False):
    """Full PCoA of a raw similarity Gramian: center → eigendecompose.

    Args:
      g: (N, N) similarity/co-occurrence matrix.
      k: number of principal coordinates.
      scale: if True, scale coordinates by sqrt(max(λ, 0)) — classical
        PCoA/Torgerson coordinates. The reference does NOT scale (it emits
        raw eigenvector entries), so the default is False.

    Returns:
      ``(coords, eigvals)`` as in :func:`principal_components`.

    The jitted body lives in ``_pcoa_jit``; this wrapper times each call
    as a ``pcoa`` span when a telemetry session is active.
    """
    from spark_examples_tpu import obs

    with obs.span("pcoa", n=int(g.shape[0]), k=int(k)):
        return _pcoa_jit(g, k, scale)


def mllib_principal_components_reference(g, k):
    """Literal numpy-f64 emulation of the reference math — the golden path.

    Mirrors ``VariantsPca.scala:198-231`` + MLlib ``RowMatrix
    .computePrincipalComponents``: double-center G, form the row covariance
    ``(CᵀC − n·μμᵀ)/(n−1)`` exactly as MLlib's ``computeCovariance`` does,
    eigendecompose, take top-k by eigenvalue descending, normalize signs.
    Runs on the host in float64 — the analog of the reference's driver-side
    Breeze/LAPACK eig.
    """
    g = np.asarray(g, dtype=np.float64)
    n = g.shape[0]
    rowmean = g.mean(axis=1, keepdims=True)
    colmean = g.mean(axis=0, keepdims=True)
    c = g - rowmean - colmean + g.mean()
    mu = c.mean(axis=0, keepdims=True)
    cov = (c.T @ c - n * (mu.T @ mu)) / (n - 1)
    w, v = np.linalg.eigh(cov)
    order = np.argsort(-w)[:k]
    return normalize_eigvec_signs(v[:, order]), w[order]
