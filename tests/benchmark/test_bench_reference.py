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


@pytest.mark.parametrize("devices,panel", [(1, 8192), (1, 100), (4, 8192),
                                           (4, 64), (3, 50)])
def test_tiled_gramian_equals_numpy_x_xt(cohort, devices, panel):
    """Row panels over 1 to 4 devices, one or several rounds of them."""
    import jax

    x = _dense(cohort)
    got = reference.gramian(cohort, jax.devices()[:devices], block=1000,
                            panel=panel)
    assert got.dtype == np.float32
    np.testing.assert_array_equal(got, x.T @ x)


def _iterative(monkeypatch):
    monkeypatch.setattr(reference, "DENSE_LIMIT", 16)


@pytest.mark.parametrize("config", ["kgp3_chr20", "nygc_chr20_rare"])
@pytest.mark.parametrize("seed", [3, 2**33 + 1])
def test_iterative_top_k_agrees_with_dense_eigh(monkeypatch, capsys, config,
                                                seed):
    cfg = bh.load_config(config)
    c = structured_sites.generate(bh.tiny_cohort(cfg, 30000), seed)
    g = reference.gramian(c)
    dense = reference.coordinates(g, 2)
    _iterative(monkeypatch)
    got = reference.coordinates(g, 2)
    assert got.shape == dense.shape
    assert reference.coord_err(got, dense) < 1e-10
    assert "converged in" in capsys.readouterr().err


def test_iterative_top_k_raises_when_it_has_not_converged(cohort):
    g = reference.gramian(cohort).astype(np.float64)
    c = g - g.mean(0) - g.mean(1)[:, None] + g.mean()
    with pytest.raises(RuntimeError, match="did not converge in 2 iter"):
        reference.top_eigvecs_iterative(lambda v: c @ v, c.shape[0], 2,
                                        max_iter=2)


@pytest.mark.parametrize("config", ["kgp3_chr20", "nygc_chr20_rare",
                                    "nygc_chr20_rare_full"])
def test_control_fails_the_limits_through_the_iterative_path(monkeypatch,
                                                             config):
    cfg = bh.load_config(config)
    c = structured_sites.generate(bh.tiny_cohort(cfg, 30000), 7)
    g = reference.gramian(c)
    _iterative(monkeypatch)
    want = reference.coordinates(g, 2)
    err = reference.coord_err(reference.control_coordinates(g, 2), want)
    assert err > 10 * cfg["limits"]["coord_err"]
    assert g.max() > 256
    assert reference.g_err(reference.control_gramian(g), g) > cfg["limits"][
        "g_err"]
