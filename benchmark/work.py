"""Least work and least time of each layer, from the cohort's own counts.

These count what the answer needs, not what an implementation does, so
the same cohort gets the same least time whatever route or kernel the
program takes; a roofline share built on them cannot pass 100% by moving
work between routes.

The least time is per chip: the whole work over ``chips`` times one
chip's peaks, to set against device time averaged over the cell's chips
(``benchmark/trace.py:module_seconds``).
"""

from __future__ import annotations


def _least(ops: float, ops_peak: float, nbytes: float, bw: float,
           chips: int) -> dict:
    t_ops, t_bytes = ops / (chips * ops_peak), nbytes / (chips * bw)
    return {
        "ops": ops,
        "bytes": nbytes,
        "seconds": max(t_ops, t_bytes),
        "bound": "compute" if t_ops >= t_bytes else "memory",
    }


def gramian_least(n: int, v: int, nnz: int, sum_k2: int, peaks: dict,
                  chips: int = 1) -> dict:
    """G = X X^T of a 0/1 cohort, N samples by V sites.

    Operations: 2 * sum_v k_v^2, one multiply-add for every ordered pair of
    carriers of every site (k_v carriers at site v), at the int8 peak.
    Bytes: the cohort read once in its smaller form, N*V/8 as bits or
    4 bytes per carrier as indices, plus G written once in float32.
    """
    ops = 2.0 * sum_k2
    nbytes = min(n * v / 8.0, 4.0 * nnz) + 4.0 * n * n
    return _least(ops, peaks["int8_ops"], nbytes, peaks["hbm_bytes_per_s"],
                  chips)


def finish_least(n: int, k: int, peaks: dict, chips: int = 1) -> dict:
    """Top-k eigenvectors of the centred N x N Gramian.

    Operations: 2 * N^2 * k, one product of G with the k wanted vectors,
    at the bf16 peak. Bytes: G read once in float32.
    """
    ops = 2.0 * n * n * k
    nbytes = 4.0 * n * n
    return _least(ops, peaks["bf16_flops"], nbytes, peaks["hbm_bytes_per_s"],
                  chips)
