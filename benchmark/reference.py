"""The plain reference, built from the generated cohort alone.

It imports nothing of the program and takes nothing the program made:

- :func:`gramian`: G = X X^T from the cohort's CSR, a block of sites at a
  time densified on the host and multiplied on the device in float32 at
  the highest matmul precision (0/1 inputs and integer sums below 2^24
  are exact there).
- :func:`coordinates`: the top-k eigenvectors of the double-centred G in
  float64 on the host (G is positive semi-definite, so the largest
  eigenvalues are the largest in magnitude).
- :func:`control_coordinates`: the same computed a precision lower than
  the configuration states, G and the centred matrix held in bfloat16;
  the comparison has to fail it.
"""

from __future__ import annotations

import numpy as np

_BLOCK = 8192


def gramian(cohort, block: int = _BLOCK) -> np.ndarray:
    import jax
    import jax.numpy as jnp

    n, v = cohort.n_samples, cohort.n_sites

    @jax.jit
    def step(g, x):
        xf = x.astype(jnp.float32)
        return g + jax.lax.dot_general(
            xf, xf, (((0,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32,
        )

    g = jnp.zeros((n, n), jnp.float32)
    offsets, ords = cohort.offsets, cohort.ords
    for a in range(0, v, block):
        b = min(a + block, v)
        lens = np.diff(offsets[a:b + 1])
        x = np.zeros((block, n), dtype=np.int8)
        x[np.repeat(np.arange(b - a), lens), ords[offsets[a]:offsets[b]]] = 1
        g = step(g, x)
    return np.asarray(g)


def _center(g: np.ndarray, dtype) -> np.ndarray:
    g = g.astype(dtype)
    return (g - g.mean(axis=0, keepdims=True) - g.mean(axis=1, keepdims=True)
            + g.mean())


def _top_eigvecs(c: np.ndarray, k: int) -> np.ndarray:
    from scipy.linalg import eigh

    n = c.shape[0]
    w, vecs = eigh(c, subset_by_index=[n - k, n - 1])
    return vecs[:, np.argsort(-w)].astype(np.float64)


def coordinates(g: np.ndarray, k: int) -> np.ndarray:
    """(N, k) principal coordinates of G in float64."""
    return _top_eigvecs(_center(g, np.float64), k)


def control_coordinates(g: np.ndarray, k: int) -> np.ndarray:
    """The reference one precision down: bfloat16 storage, float32 maths."""
    import ml_dtypes

    bf16 = ml_dtypes.bfloat16
    g16 = g.astype(bf16).astype(np.float32)
    c16 = _center(g16, np.float32).astype(bf16).astype(np.float32)
    return _top_eigvecs(c16, k)


def control_gramian(g: np.ndarray) -> np.ndarray:
    """G held in bfloat16: counts above 256 lose their low bits."""
    import ml_dtypes

    return g.astype(ml_dtypes.bfloat16).astype(np.float32)


def coord_err(got: np.ndarray, want: np.ndarray) -> float:
    """Largest coordinate difference, each column's sign taken to agree
    with the reference first (an eigenvector's sign is arbitrary)."""
    got = np.asarray(got, dtype=np.float64)
    signs = np.sign(np.sum(got * want, axis=0))
    signs[signs == 0] = 1.0
    return float(np.max(np.abs(got * signs - want)))
