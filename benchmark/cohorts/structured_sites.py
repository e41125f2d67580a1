"""Cohort generator: sites in frequency classes over a population tree.

Reads the ``cohort`` section of a configuration file:

- ``populations``: ``[name, samples]`` pairs; samples are numbered
  population by population.
- ``sites``, ``contig``, ``span_bases``: the site count, and the contig
  and its first ``span_bases`` bases that the sites are spread over.
- ``structure_seed``: draws the cohort's structure X0: which site is in
  which class, every frequency and every carrier. The run's seed then
  relabels the samples by a random permutation within each population,
  so every seed gets the same sites with the same carrier count per site
  and per population, carried by other samples (the same sizes in
  another order: the program's arrays have the same shapes and lengths
  for every seed).
- ``classes``: the frequency spectrum as shares of the sites. Each site
  belongs to one class; the number of sites in each class, and in each
  (population, carrier count) group of a private class, are exact. Two
  kinds:

  - ``drift``: the ancestral alternate-allele frequency is log-uniform
    over ``freq`` (density proportional to 1/f, the neutral spectrum), and
    each population's frequency drifts from it down ``drift_tree``
    (Balding-Nichols: Beta(p(1-F)/F, (1-p)(1-F)/F) on each edge of
    fixation index F), with ``admixed`` populations a mixture of tree
    nodes. A sample carries the site with probability 1-(1-p)^2 (a
    diploid with at least one alternate allele). A site nobody carries
    gets one carrier, so the site count is exact.
  - ``private``: the site lies in one population, chosen in proportion to
    its size times ``population_weights``; it has k carriers, k drawn in
    proportion to 1/k over 1..``max_carriers``, distinct samples of that
    population chosen uniformly.
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from benchmark.cohortlib import (
    Cohort,
    distinct_draws,
    even_starts,
    largest_remainder,
)

# Sites per Bernoulli chunk: one (chunk, N) uint16 draw, ~40 MB at N=2,504.
_CHUNK = 8192


def generate(cfg: dict, seed: int) -> Cohort:
    pops = [(str(name), int(n)) for name, n in cfg["populations"]]
    names = [name for name, _ in pops]
    sizes = np.array([n for _, n in pops], dtype=np.int64)
    bounds = np.concatenate([[0], np.cumsum(sizes)])
    n_samples = int(bounds[-1])
    v = int(cfg["sites"])
    classes = cfg["classes"]
    structure = int(cfg["structure_seed"])
    rng = np.random.default_rng([structure, 0])

    counts = largest_remainder([c["share"] for c in classes], v)
    site_class = rng.permutation(np.repeat(np.arange(len(classes)), counts))
    lens = np.zeros(v, dtype=np.int64)
    parts = []  # (sites, per-site carrier counts, concatenated ordinals)
    for ci, cls in enumerate(classes):
        sites = np.flatnonzero(site_class == ci)
        if cls["kind"] == "drift":
            freqs = _drift_frequencies(cfg, cls, names, sites.size, rng)
            k, ords = _bernoulli_carriers(freqs, bounds, structure, ci)
        elif cls["kind"] == "private":
            k, ords = _private_carriers(cls, names, sizes, bounds,
                                        sites.size, rng)
        else:
            raise ValueError(f"unknown site class kind {cls['kind']!r}")
        lens[sites] = k
        parts.append((sites, k, ords))

    offsets = np.zeros(v + 1, dtype=np.int64)
    np.cumsum(lens, out=offsets[1:])
    ords = np.empty(int(offsets[-1]), dtype=np.int32)
    for sites, k, part in parts:
        _scatter_rows(ords, offsets[sites], k, part)
    relabel = np.random.default_rng([seed % (1 << 64), 1])  # any integer
    perm = np.concatenate([lo + relabel.permutation(n) for lo, n in
                           zip(bounds[:-1], sizes)]).astype(np.int32)
    ords = perm[ords]
    return Cohort(
        contig=str(cfg["contig"]),
        span_bases=int(cfg["span_bases"]),
        sample_ids=[f"S{i:05d}" for i in range(n_samples)],
        pop_of_sample=np.repeat(np.arange(len(pops)), sizes),
        starts=even_starts(v, int(cfg["span_bases"])),
        offsets=offsets,
        ords=ords,
    )


def _drift_frequencies(cfg, cls, names, m, rng) -> np.ndarray:
    """(m, P) alternate-allele frequency per site and population."""
    lo, hi = cls["freq"]
    node = {"root": lo * (hi / lo) ** rng.random(m)}
    for child, parent, fst in cfg["drift_tree"]:
        p = np.clip(node[parent], 1e-9, 1 - 1e-9)
        scale = (1.0 - fst) / fst
        node[child] = rng.beta(p * scale, (1.0 - p) * scale)
    for pop, mix in cfg.get("admixed", {}).items():
        node[pop] = sum(w * node[src] for src, w in mix.items())
    return np.stack([node[name] for name in names], axis=1)


def _bernoulli_carriers(freqs, bounds, seed, ci):
    """Each sample carries each site with its population's carrier
    probability; chunks draw from their own streams, so the result does
    not depend on how threads interleave."""
    n = int(bounds[-1])
    thr = np.rint((1.0 - (1.0 - freqs) ** 2) * 65536).astype(np.uint32)

    def chunk(j):
        rng = np.random.default_rng([seed, 1 + ci, j])
        t = thr[j * _CHUNK:(j + 1) * _CHUNK]
        r = rng.integers(0, 65536, size=(t.shape[0], n), dtype=np.uint16)
        mask = np.empty(r.shape, dtype=bool)
        for p in range(t.shape[1]):
            lo, hi = bounds[p], bounds[p + 1]
            mask[:, lo:hi] = r[:, lo:hi] < t[:, p, None]
        empty = np.flatnonzero(~mask.any(axis=1))
        mask[empty, rng.integers(0, n, size=empty.size)] = True
        k = mask.sum(axis=1)
        return k, (np.flatnonzero(mask) % n).astype(np.int32)

    n_chunks = -(-freqs.shape[0] // _CHUNK)
    with ThreadPoolExecutor(max_workers=os.cpu_count() or 1) as ex:
        done = list(ex.map(chunk, range(n_chunks)))
    if not done:
        return np.zeros(0, np.int64), np.zeros(0, np.int32)
    return (np.concatenate([k for k, _ in done]),
            np.concatenate([o for _, o in done]))


def _private_carriers(cls, names, sizes, bounds, m, rng):
    """Per-site carrier counts and ordinals (site order) of a private class."""
    kmax = int(cls["max_carriers"])
    weights = np.array(
        [sizes[i] * cls["population_weights"][name]
         for i, name in enumerate(names)], dtype=np.float64,
    )
    ks = np.arange(1, kmax + 1)
    grid = largest_remainder(np.outer(weights, 1.0 / ks).ravel(), m)
    group = rng.permutation(np.repeat(np.arange(grid.size), grid))
    pop, k = np.divmod(group, kmax)
    k = k + 1
    starts = np.zeros(m + 1, dtype=np.int64)
    np.cumsum(k, out=starts[1:])
    ords = np.empty(int(starts[-1]), dtype=np.int32)
    by_group = np.split(np.argsort(group, kind="stable"),
                        np.cumsum(grid)[:-1])
    for g in np.flatnonzero(grid):
        p, kk = divmod(int(g), kmax)
        kk += 1
        rows = by_group[g]
        draws = distinct_draws(rng, int(sizes[p]), kk, rows.size)
        pos = starts[rows][:, None] + np.arange(kk)
        ords[pos] = draws + bounds[p]
    return k, ords


def _scatter_rows(out, dest, lens, src) -> None:
    """Copy CSR rows (``lens`` long, concatenated in ``src``) to ``out``
    at row starts ``dest``: long rows one slice each, short rows by one
    element scatter per block of rows (the faster way for each)."""
    src_starts = np.zeros(lens.size + 1, dtype=np.int64)
    np.cumsum(lens, out=src_starts[1:])
    if lens.size and src.size >= 32 * lens.size:
        d, s = dest.tolist(), src_starts.tolist()
        for i in range(lens.size):
            out[d[i]:d[i] + s[i + 1] - s[i]] = src[s[i]:s[i + 1]]
        return
    step = 1 << 16
    for a in range(0, lens.size, step):
        b = min(a + step, lens.size)
        ln = lens[a:b]
        shift = np.repeat(dest[a:b] - src_starts[a:b], ln)
        lo, hi = src_starts[a], src_starts[b]
        out[shift + np.arange(lo, hi)] = src[lo:hi]
