"""Cohorts for the benchmark: the in-memory form, the on-disk form, and the
sampling helpers the generators under ``benchmark/cohorts/`` share.

A cohort is a 0/1 carrier matrix X (V sites x N samples) held as CSR:
``offsets`` (V+1, int64) and ``ords`` (nnz, int32), where the carriers of
site v are ``ords[offsets[v]:offsets[v+1]]``, sample ordinals in
``callsets.json`` order. Generators write it straight in that form from
the seed; no per-call record is ever built.

On disk it is a light cohort mirror, the layout a client holds after it
mirrored a served cohort with ``--mirror-mode light``: ``callsets.json``,
the columnar CSR sidecar ``.variants.csr.npz`` and the three mirror marker
files that make the sidecar trusted without a ``variants.jsonl``. The
layout below is the program's documented input format (sidecar format
version 2); the benchmark writes it with numpy alone.
"""

from __future__ import annotations

import json
import os
from dataclasses import dataclass
from typing import List, Sequence

import numpy as np

SIDECAR_VERSION = 2
SIDECAR_NAME = ".variants.csr.npz"
MIRROR_MARKERS = (".complete", ".identity", ".sidecar-ok")


@dataclass
class Cohort:
    """One generated cohort: sample table, site positions and CSR carriers."""

    contig: str
    span_bases: int  # the sites lie in [0, span_bases) of the contig
    sample_ids: List[str]
    pop_of_sample: np.ndarray  # (N,) population index, config order
    starts: np.ndarray  # (V,) int64, sorted
    offsets: np.ndarray  # (V+1,) int64
    ords: np.ndarray  # (nnz,) int32

    @property
    def n_samples(self) -> int:
        return len(self.sample_ids)

    @property
    def n_sites(self) -> int:
        return int(self.starts.size)

    @property
    def nnz(self) -> int:
        return int(self.offsets[-1])

    def carriers_per_site(self) -> np.ndarray:
        return np.diff(self.offsets)

    def sum_k2(self) -> int:
        """Sum over sites of the squared carrier count (the pair count of G)."""
        k = self.carriers_per_site().astype(np.int64)
        return int(np.dot(k, k))

    def references(self) -> str:
        """The ``--references`` value that covers the whole cohort."""
        return f"{self.contig}:0:{self.span_bases}"


def largest_remainder(shares: Sequence[float], total: int) -> np.ndarray:
    """Integer counts proportional to ``shares`` that sum to ``total``;
    the same for every seed, so every seed gets the same set of sizes."""
    shares = np.asarray(shares, dtype=np.float64)
    exact = shares / shares.sum() * total
    counts = np.floor(exact).astype(np.int64)
    short = total - int(counts.sum())
    order = np.argsort(-(exact - counts), kind="stable")
    counts[order[:short]] += 1
    return counts


def even_starts(n_sites: int, span_bases: int) -> np.ndarray:
    """Site positions spread evenly over ``[60 kb, span_bases - 60 kb)``
    (the telomeric ends hold no sites). Positions fix only how sites fall
    into the program's 1 Mb shards, so they are the same for every seed."""
    lo, hi = 60_000, span_bases - 60_000
    return lo + (np.arange(n_sites, dtype=np.int64) * (hi - lo)) // n_sites


def distinct_draws(rng, n: int, k: int, m: int) -> np.ndarray:
    """(m, k) sample indices in [0, n), distinct within each row, each row
    a uniform k-subset. Draws with replacement and redraws the repeats
    until none are left (k is at most a fifth of n here, so a few rounds)."""
    if k > n:
        raise ValueError(f"cannot draw {k} distinct samples from {n}")
    out = np.sort(rng.integers(0, n, size=(m, k)), axis=1)
    while k > 1:
        dup = np.zeros_like(out, dtype=bool)
        dup[:, 1:] = out[:, 1:] == out[:, :-1]
        count = int(dup.sum())
        if count == 0:
            break
        out[dup] = rng.integers(0, n, size=count)
        out.sort(axis=1)
    return out


def write_light_mirror(cohort: Cohort, root: str) -> None:
    """Write ``cohort`` to ``root`` as a light mirror (see module doc)."""
    os.makedirs(root, exist_ok=True)
    with open(os.path.join(root, "callsets.json"), "w") as f:
        json.dump(
            [{"id": cid, "name": cid} for cid in cohort.sample_ids], f
        )
    v = cohort.n_sites
    data = {
        "digest": np.str_(f"v{SIDECAR_VERSION}|benchmark-light-mirror"),
        "contigs": np.array([cohort.contig]),
        "seg_lo": np.array([0], dtype=np.int64),
        "seg_hi": np.array([v], dtype=np.int64),
        "starts": cohort.starts.astype(np.int64, copy=False),
        "vcode": np.zeros(v, dtype=np.int32),
        "afs": np.full(v, np.nan),
        "offsets": cohort.offsets.astype(np.int64, copy=False),
        "ords": cohort.ords.astype(np.int32, copy=False),
        "vsids": np.array([""]),
        "callset_ids": np.array(cohort.sample_ids),
    }
    with open(os.path.join(root, SIDECAR_NAME), "wb") as f:
        np.savez(f, **data)
        # Written back now, in set-up, not by the kernel during the window.
        f.flush()
        os.fsync(f.fileno())
    identity = "benchmark-light-mirror"
    for name, text in zip(MIRROR_MARKERS, ("", identity, identity)):
        with open(os.path.join(root, name), "w") as f:
            f.write(text)
