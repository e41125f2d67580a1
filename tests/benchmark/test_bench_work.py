"""The least-work functions against hand-computed examples."""

import pytest

import bench_helpers  # noqa: F401 — the checkout on sys.path
from benchmark import work
from benchmark.peaks import DEVICE_PEAKS, peaks_for

V5E = DEVICE_PEAKS["TPU v5 lite"]


def test_gramian_least_compute_bound():
    # 4 samples, 2 sites carried by 3 and 1 samples: pairs 9 + 1 = 10.
    peaks = {"int8_ops": 20.0, "hbm_bytes_per_s": 1e9, "bf16_flops": 1.0}
    least = work.gramian_least(4, 2, 4, 10, peaks)
    assert least["ops"] == 20.0
    # min(4*2/8 bits, 4*4 index bytes) + 4*4^2 for G = 1 + 64.
    assert least["bytes"] == 65.0
    assert least["seconds"] == 1.0 and least["bound"] == "compute"


def test_gramian_least_memory_bound_picks_the_smaller_cohort_form():
    peaks = {"int8_ops": 1e12, "hbm_bytes_per_s": 100.0, "bf16_flops": 1.0}
    # 8 samples x 80 sites: bits 80 B; 5 carriers as indices 20 B.
    least = work.gramian_least(8, 80, 5, 5, peaks)
    assert least["bytes"] == 20.0 + 4 * 64
    assert least["seconds"] == pytest.approx(2.76)
    assert least["bound"] == "memory"


def test_finish_least_on_v5e():
    least = work.finish_least(2504, 2, V5E)
    assert least["ops"] == 2.0 * 2504 * 2504 * 2
    assert least["bytes"] == 4.0 * 2504 * 2504
    assert least["bound"] == "memory"
    assert least["seconds"] == pytest.approx(4.0 * 2504**2 / 819e9)


def test_kgp3_scale_gramian_least_on_v5e():
    # 2,504 x 1,812,841, 2.25e8 carriers, sum k^2 = 2.5e11 (about the
    # generated cohort's): compute bound at 2*2.5e11 / 393e12 s.
    least = work.gramian_least(2504, 1812841, 225_000_000, 250_000_000_000,
                               V5E)
    assert least["bound"] == "compute"
    assert least["seconds"] == pytest.approx(5e11 / 393e12)
    assert least["bytes"] == pytest.approx(2504 * 1812841 / 8 + 4 * 2504**2)


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError, match="no published peaks"):
        peaks_for("TPU v99")


@pytest.mark.parametrize("chips", [2, 4])
def test_least_time_is_per_chip(chips):
    """The whole work over chips times one chip's peaks; the work itself
    and which peak bounds it do not change."""
    one = work.gramian_least(2504, 1812841, 225_000_000, 250_000_000_000,
                             V5E)
    many = work.gramian_least(2504, 1812841, 225_000_000, 250_000_000_000,
                              V5E, chips=chips)
    assert (many["ops"], many["bytes"], many["bound"]) == (
        one["ops"], one["bytes"], one["bound"])
    assert many["seconds"] == pytest.approx(one["seconds"] / chips)
    fin = work.finish_least(49960, 2, V5E, chips=chips)
    assert fin["seconds"] == pytest.approx(4.0 * 49960**2 / (chips * 819e9))


def test_one_chip_least_time_is_unchanged_to_the_digit():
    peaks = {"int8_ops": 393e12, "hbm_bytes_per_s": 819e9,
             "bf16_flops": 197e12}
    least = work.gramian_least(3202, 217244, 2930749, 91_000_000, peaks)
    assert least == work.gramian_least(3202, 217244, 2930749, 91_000_000,
                                       peaks, chips=1)
    assert least["seconds"] == max(2.0 * 91_000_000 / 393e12,
                                   (4.0 * 2930749 + 4.0 * 3202**2) / 819e9)
    assert work.finish_least(3202, 2, peaks)["seconds"] == (
        4.0 * 3202**2 / 819e9)
