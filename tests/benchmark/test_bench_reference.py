"""The plain reference and the control the comparison has to fail."""

import numpy as np
import pytest

import bench_helpers as bh
from benchmark import reference
from benchmark.cohorts import structured_sites


@pytest.fixture(scope="module")
def cohort():
    cfg = bh.load_config("kgp3_chr20")
    return structured_sites.generate(bh.tiny_cohort(cfg, 12000), 99)


def _dense(cohort):
    x = np.zeros((cohort.n_sites, cohort.n_samples), np.int64)
    rows = np.repeat(np.arange(cohort.n_sites), cohort.carriers_per_site())
    x[rows, cohort.ords] = 1
    return x


def test_gramian_equals_numpy_x_xt(cohort):
    x = _dense(cohort)
    want = x.T @ x
    got = reference.gramian(cohort, block=512)
    assert got.dtype == np.float32
    np.testing.assert_array_equal(got, want)
    assert cohort.sum_k2() == int(want.sum())  # sum_ij G_ij = sum_v k_v^2


def test_coordinates_are_the_top_eigenvectors(cohort):
    g = reference.gramian(cohort).astype(np.float64)
    c = g - g.mean(0) - g.mean(1)[:, None] + g.mean()
    w, v = np.linalg.eigh(c)
    want = v[:, np.argsort(-w)[:2]]
    got = reference.coordinates(g, 2)
    assert reference.coord_err(got, want) < 1e-9
    assert reference.coord_err(-got, want) < 1e-9  # signs are aligned


def test_control_is_a_lower_precision_and_fails_the_limits(cohort):
    cfg = bh.load_config("kgp3_chr20")
    g = reference.gramian(cohort)
    want = reference.coordinates(g, 2)
    assert g.max() > 256  # counts bfloat16 cannot hold exactly
    assert np.abs(reference.control_gramian(g) - g).max() > cfg["limits"]["g_err"]
    err = reference.coord_err(reference.control_coordinates(g, 2), want)
    assert err > 0.0
    assert reference.coord_err(want, want) == 0.0
