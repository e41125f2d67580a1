"""The plain reference, built from the generated cohort alone.

It imports nothing of the program and takes nothing the program made:

- :func:`gramian`: G = X X^T from the cohort's CSR, in row panels of at
  most ``panel`` rows spread over the devices it is given, so that no
  device holds more than one panel of G. A device densifies a block of
  sites at a time from the CSR (int8 0/1), multiplies its panel's columns
  of the block with the whole block on the int8 matrix unit and
  accumulates in int32: exact for every count. G comes back to the host
  as float32, exact for every count below 2^24.
- :func:`coordinates`: the top-k eigenvectors of the double-centred G in
  float64 on the host (G is positive semi-definite, so the largest
  eigenvalues are the largest in magnitude): a dense ``eigh`` up to
  :data:`DENSE_LIMIT` samples; above it subspace iteration with
  Rayleigh-Ritz, whose products with the centred matrix run over row
  blocks of the float32 G in float64 and never build that matrix. It
  stops when every wanted pair's residual is at most :data:`TOL` times
  the largest eigenvalue, and raises if it has not within its cap.
- :func:`control_coordinates`, :func:`control_gramian`: the same computed
  a precision lower than the configuration states, G and the centred
  matrix held in bfloat16; the comparison has to fail them.
- :func:`g_err`: the largest difference of two Gramians, a row block at
  a time in float32 (the differences of counts below 2^24 are exact).
"""

from __future__ import annotations

import os
import sys
from concurrent.futures import ThreadPoolExecutor

import numpy as np

_BLOCK = 8192  # sites densified per step
_PANEL = 8192  # most rows of G one device holds
_ROWS = 64  # rows of G per host product task (a 25 MB float64 block at N = 50k)
_CMP_ROWS = 1024  # rows of G per comparison step (4 MB of float32 per 1,000 samples)

# Above this many samples the top-k is iterative: a dense float64 eigh is
# O(N^3) on the host and the centred matrix 8 N^2 bytes.
DENSE_LIMIT = 16384
TOL = 1e-10
MAX_ITER = 200
_EXTRA = 14  # block size of the iteration beyond the k wanted vectors


def gramian(cohort, devices=None, block: int = _BLOCK,
            panel: int = _PANEL) -> np.ndarray:
    import jax
    import jax.numpy as jnp

    devices = list(devices) if devices else jax.devices()[:1]
    n, v = cohort.n_samples, cohort.n_sites
    rounds = -(-n // (len(devices) * panel))
    height = -(-n // (rounds * len(devices)))
    height = -(-height // 128) * 128
    width = height * rounds * len(devices)
    if block * width >= 2**31:
        raise ValueError(f"a block of {block} sites x {width} is too large "
                         "for int32 indices")

    def step(g, flat, r0):
        x = jnp.zeros(block * width, jnp.int8).at[flat].set(
            1, mode="drop").reshape(block, width)
        lhs = jax.lax.dynamic_slice_in_dim(x, r0, height, axis=1)
        return g + jax.lax.dot_general(
            lhs, x, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.int32)

    step = jax.jit(step, donate_argnums=0)
    offsets, ords = cohort.offsets, cohort.ords
    starts = range(0, v, block)
    cap = max((int(offsets[min(a + block, v)] - offsets[a]) for a in starts),
              default=0)
    cap = -(-max(cap, 1) // 1024) * 1024
    out = np.empty((n, n), np.float32)
    for r in range(rounds):
        r0s = [(r * len(devices) + d) * height for d in range(len(devices))]
        gs = []
        for dev in devices:
            # Made on its own device: ``jnp.zeros(..., device=dev)`` fills
            # on the default device and copies, so that device would hold
            # every other device's panel for a moment too.
            with jax.default_device(dev):
                gs.append(jax.device_put(jnp.zeros((height, width),
                                                   jnp.int32), dev))
        for a in starts:
            b = min(a + block, v)
            lens = np.diff(offsets[a:b + 1])
            flat = np.full(cap, block * width, np.int32)  # past the end: dropped
            nnz = int(offsets[b] - offsets[a])
            flat[:nnz] = (np.repeat(np.arange(b - a, dtype=np.int32) * width,
                                    lens) + ords[offsets[a]:offsets[b]])
            gs = [step(g, jax.device_put(flat, dev), r0)
                  for g, dev, r0 in zip(gs, devices, r0s)]
        for g, r0 in zip(gs, r0s):
            if r0 < n:
                rows = min(height, n - r0)
                out[r0:r0 + rows] = np.asarray(g)[:rows, :n]
        del gs, g
    return out


# -- top-k eigenvectors -------------------------------------------------------


def _center(g: np.ndarray, dtype) -> np.ndarray:
    g = g.astype(dtype)
    return (g - g.mean(axis=0, keepdims=True) - g.mean(axis=1, keepdims=True)
            + g.mean())


def _top_eigvecs(c: np.ndarray, k: int) -> np.ndarray:
    from scipy.linalg import eigh

    n = c.shape[0]
    w, vecs = eigh(c, subset_by_index=[n - k, n - 1])
    return vecs[:, np.argsort(-w)].astype(np.float64)


def _row_products(rows_of, n: int, v: np.ndarray) -> np.ndarray:
    """M V in float64, where ``rows_of(a, b)`` gives rows a..b of M in
    float64; a row block per task over the host's cores, the BLAS held to
    one thread in each."""
    from threadpoolctl import threadpool_limits

    out = np.empty((n, v.shape[1]))

    def task(a):
        b = min(a + _ROWS, n)
        np.matmul(rows_of(a, b), v, out=out[a:b])

    workers = min(os.cpu_count() or 1, 32)
    with threadpool_limits(limits=1, user_api="blas"), \
            ThreadPoolExecutor(max_workers=workers) as ex:
        list(ex.map(task, range(0, n, _ROWS)))
    return out


def top_eigvecs_iterative(apply, n: int, k: int, max_iter: int = MAX_ITER,
                          tol: float = TOL) -> np.ndarray:
    """(n, k) top eigenvectors of a symmetric positive semi-definite
    operator, ``apply(V)`` = M V, by subspace iteration with Rayleigh-Ritz
    in float64 from a fixed start. Stops when each wanted Ritz pair has
    ||M x - theta x|| <= ``tol`` * theta_1; raises after ``max_iter``
    products without that."""
    p = min(n, k + _EXTRA)
    v, _ = np.linalg.qr(np.random.default_rng(0).standard_normal((n, p)))
    for it in range(1, max_iter + 1):
        w = apply(v)
        h = v.T @ w
        theta, s = np.linalg.eigh(0.5 * (h + h.T))
        order = np.argsort(-theta)
        theta, s = theta[order], s[:, order]
        x, mx = v @ s, w @ s
        res = np.linalg.norm(mx[:, :k] - x[:, :k] * theta[:k], axis=0)
        if np.all(res <= tol * theta[0]):
            print(f"reference: top-{k} converged in {it} iterations, "
                  f"residual / lambda_1 {float(res.max() / theta[0]):.3g}",
                  file=sys.stderr, flush=True)
            return x[:, :k]
        v, _ = np.linalg.qr(mx)
    raise RuntimeError(
        f"top-{k} eigenvectors did not converge in {max_iter} iterations: "
        f"residual / lambda_1 {float(res.max() / theta[0]):.3g} > {tol}")


def _centred_apply(rows_of, n: int):
    """C V for C = J G J, J the centring, from products with G alone:
    C V = G V - 1 (m^T V) - m (1^T V) + mu 1 (1^T V), m the row means of
    G and mu their mean."""
    m = _row_products(rows_of, n, np.full((n, 1), 1.0 / n))[:, 0]
    mu = m.mean()

    def apply(v):
        col = v.sum(axis=0, keepdims=True)
        return (_row_products(rows_of, n, v) - (m @ v)[None, :]
                - m[:, None] * col + mu * col)

    return apply


def coordinates(g: np.ndarray, k: int) -> np.ndarray:
    """(N, k) principal coordinates of G in float64."""
    n = g.shape[0]
    if n <= DENSE_LIMIT:
        return _top_eigvecs(_center(g, np.float64), k)
    apply = _centred_apply(lambda a, b: g[a:b].astype(np.float64), n)
    return top_eigvecs_iterative(apply, n, k)


def control_coordinates(g: np.ndarray, k: int) -> np.ndarray:
    """The reference one precision down: bfloat16 storage, float32 maths."""
    import ml_dtypes

    bf16 = ml_dtypes.bfloat16
    n = g.shape[0]
    if n <= DENSE_LIMIT:
        g16 = g.astype(bf16).astype(np.float32)
        c16 = _center(g16, np.float32).astype(bf16).astype(np.float32)
        return _top_eigvecs(c16, k)

    def g16(a, b):
        return g[a:b].astype(bf16).astype(np.float32)

    m = _row_products(lambda a, b: g16(a, b).astype(np.float64), n,
                      np.full((n, 1), 1.0 / n))[:, 0].astype(np.float32)
    mu = m.mean(dtype=np.float32)

    def c16(a, b):
        # m_i + m_j is the same sum for (i, j) and (j, i): C16 is symmetric.
        c = (g16(a, b) - (m[a:b, None] + m[None, :])) + mu
        return c.astype(bf16).astype(np.float64)

    return top_eigvecs_iterative(
        lambda v: _row_products(c16, n, v), n, k)


def control_gramian(g: np.ndarray) -> np.ndarray:
    """G held in bfloat16: counts above 256 lose their low bits."""
    import ml_dtypes

    return g.astype(ml_dtypes.bfloat16).astype(np.float32)


# -- comparison ---------------------------------------------------------------


def permuted(g: np.ndarray, perm: np.ndarray) -> np.ndarray:
    """G with rows and columns in ``perm`` order (G itself when that is
    the order it has)."""
    if np.array_equal(perm, np.arange(g.shape[0])):
        return g
    return g[np.ix_(perm, perm)]


def g_err(got: np.ndarray, want: np.ndarray) -> float:
    """max |got - want|, a row block at a time in float32."""
    err = 0.0
    for a in range(0, want.shape[0], _CMP_ROWS):
        d = np.abs(got[a:a + _CMP_ROWS].astype(np.float32, copy=False)
                   - want[a:a + _CMP_ROWS])
        err = max(err, _finite_or_inf(d.max(initial=0.0)))
    return err


def coord_err(got: np.ndarray, want: np.ndarray) -> float:
    """Largest coordinate difference, each column's sign taken to agree
    with the reference first (an eigenvector's sign is arbitrary)."""
    got = np.asarray(got, dtype=np.float64)
    signs = np.sign(np.sum(got * want, axis=0))
    signs[signs == 0] = 1.0
    return _finite_or_inf(np.max(np.abs(got * signs - want)))


def _finite_or_inf(x) -> float:
    """A NaN difference reads as infinitely wrong, never as 0: ``max``
    keeps its first argument against a NaN."""
    return float("inf") if np.isnan(x) else float(x)
