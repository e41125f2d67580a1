"""The cohort generators: deterministic per seed, the stated sizes and
spectrum, and the rare cohort's carrier cap that keeps every window on the
scatter route."""

import numpy as np
import pytest

import bench_helpers as bh
from benchmark import cohortlib
from benchmark.cohorts import structured_sites

SEED = 2**40 + 12345  # larger than 32 signed bits hold


def _generate(name, sites, shrink=1, max_carriers=64, seed=SEED):
    cfg = bh.load_config(name)
    return structured_sites.generate(
        bh.tiny_cohort(cfg, sites, shrink, max_carriers), seed)


@pytest.mark.parametrize("name", ["kgp3_chr20", "nygc_chr20_rare",
                                  "nygc_chr20_rare_full"])
def test_same_seed_same_cohort_other_seed_other_carriers(name):
    a = _generate(name, 4000, shrink=4)
    b = _generate(name, 4000, shrink=4)
    c = _generate(name, 4000, shrink=4, seed=SEED + 1)
    assert np.array_equal(a.offsets, b.offsets)
    assert np.array_equal(a.ords, b.ords)
    assert not np.array_equal(a.ords, c.ords)
    assert np.array_equal(a.starts, c.starts)


@pytest.mark.parametrize("name,n", [("kgp3_chr20", 2504),
                                    ("nygc_chr20_rare", 3202),
                                    ("nygc_chr20_rare_full", 3202)])
def test_stated_sizes(name, n):
    cfg = bh.load_config(name)
    pops = dict(cfg["cohort"]["populations"])
    assert sum(pops.values()) == n
    cohort = _generate(name, 5000)
    assert cohort.n_samples == n
    assert cohort.n_sites == 5000
    k = cohort.carriers_per_site()
    assert k.min() >= 1  # no site is dropped for having no carrier
    assert np.all(np.diff(cohort.starts) > 0)
    assert cohort.starts[-1] < cfg["cohort"]["span_bases"]
    # Carriers are distinct samples of the cohort.
    for v in range(0, 5000, 97):
        row = cohort.ords[cohort.offsets[v]:cohort.offsets[v + 1]]
        assert len(set(row.tolist())) == row.size
        assert row.min() >= 0 and row.max() < n


def test_kgp3_spectrum_and_density():
    cohort = _generate("kgp3_chr20", 20000)
    k = cohort.carriers_per_site()
    n = cohort.n_samples
    carrier_share = k / n
    # 64:12:8 shares of sites below 0.5%, 0.5-5% and above 5% allele
    # frequency; a carrier share is about twice the allele frequency, and
    # drift takes some 0.5-5% sites under 25 carriers.
    rare = np.mean(k <= 25)
    common = np.mean(carrier_share > 0.10)
    assert 64 / 84 <= rare <= 0.87
    assert 0.07 <= common <= 8 / 84 + 0.02
    # About 5% dense: about 93k carried sites per sample at the full 1.81 M.
    density = cohort.nnz / (n * cohort.n_sites)
    assert 0.04 <= density <= 0.06


def test_nygc_carrier_cap_keeps_every_window_on_scatter():
    from spark_examples_tpu.arrays.blocks import DEFAULT_BLOCK_VARIANTS
    from spark_examples_tpu.ops.sparse import (
        DEFAULT_SPARSE_DENSITY_THRESHOLD,
        window_route,
    )

    cohort = _generate("nygc_chr20_rare", 3 * DEFAULT_BLOCK_VARIANTS)
    k = cohort.carriers_per_site()
    assert k.max() == 64 and k.min() == 1
    assert abs(k.mean() - 64 / np.sum(1.0 / np.arange(1, 65))) < 0.2
    assert 64 / cohort.n_samples < DEFAULT_SPARSE_DENSITY_THRESHOLD
    for a in range(0, k.size, DEFAULT_BLOCK_VARIANTS):
        lens = k[a:a + DEFAULT_BLOCK_VARIANTS]
        assert window_route(lens, cohort.n_samples,
                            DEFAULT_SPARSE_DENSITY_THRESHOLD) == "scatter"


def test_nygc_sites_are_private_to_one_population():
    cohort = _generate("nygc_chr20_rare", 3000)
    pop = cohort.pop_of_sample
    for v in range(cohort.n_sites):
        row = cohort.ords[cohort.offsets[v]:cohort.offsets[v + 1]]
        assert np.unique(pop[row]).size == 1


def test_every_seed_gets_the_same_sizes():
    a = _generate("nygc_chr20_rare", 6000, seed=3)
    b = _generate("nygc_chr20_rare", 6000, seed=2**41)
    assert a.nnz == b.nnz
    assert np.array_equal(np.sort(a.carriers_per_site()),
                          np.sort(b.carriers_per_site()))


def test_largest_remainder_and_distinct_draws():
    counts = cohortlib.largest_remainder([64, 12, 8], 1812841)
    assert counts.sum() == 1812841
    assert list(counts) == [1381212, 258977, 172652]
    rng = np.random.default_rng(0)
    draws = cohortlib.distinct_draws(rng, 70, 64, 500)
    assert draws.shape == (500, 64)
    assert all(len(set(r.tolist())) == 64 for r in draws)
    assert draws.min() >= 0 and draws.max() < 70


def test_light_mirror_is_read_by_the_program(tmp_path):
    from spark_examples_tpu.genomics.shards import Shard
    from spark_examples_tpu.genomics.sources import JsonlSource
    from spark_examples_tpu.utils.stats import IoStats

    cohort = _generate("kgp3_chr20", 2000, shrink=10, max_carriers=6)
    cohortlib.write_light_mirror(cohort, str(tmp_path))
    source = JsonlSource(str(tmp_path))
    ids = [c.id for c in source.list_callsets("")]
    assert ids == cohort.sample_ids
    index = {cid: i for i, cid in enumerate(ids)}
    shard = Shard("20", 0, cohort.span_bases)
    idx, offs = source.stream_carrying_csr("", shard, index, None)
    assert np.array_equal(offs, cohort.offsets)
    assert np.array_equal(idx, cohort.ords)
    assert isinstance(source.stats, IoStats)
