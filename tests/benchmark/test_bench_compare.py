"""The bounded comparison (row blocks in float32, G_ref permuted once), the
answers the window keeps within the host's budget, and the device memory
peak read over the cell's chips."""

import numpy as np
import pytest

import bench_helpers as bh
from benchmark import reference, run
from benchmark.cohorts import structured_sites

LIMITS = {"g_err": 0.0, "coord_err": 1e-6}


@pytest.fixture(scope="module")
def answers():
    cfg = bh.load_config("nygc_chr20_rare")
    cohort = structured_sites.generate(bh.tiny_cohort(cfg, 30000), 11)
    g_ref = reference.gramian(cohort)
    return cohort, g_ref, reference.coordinates(g_ref, 2)


def _old_compare(cohort, results, g_ref, c_ref):
    """The float64 comparison the bounded one replaces, as it was."""
    order = {cid: i for i, cid in enumerate(cohort.sample_ids)}
    g_err = coord_err = 0.0
    for g, coords, rows in results:
        perm = [order[cid] for cid, _ in sorted(rows.items(),
                                                 key=lambda kv: kv[1])]
        want = g_ref[perm][:, perm]
        g_err = max(g_err, float(np.abs(g.astype(np.float64) - want).max()))
        got = np.zeros((len(order), 2))
        for cid, pc1, pc2 in coords:
            got[order[cid]] = (pc1, pc2)
        coord_err = max(coord_err, reference.coord_err(got, c_ref[:, :2]))
    return g_err, coord_err


def _answer(cohort, g_ref, c_ref, perm, coord_shift=0.0):
    """The program's answer with rows in ``perm`` order: (G, coordinate
    tuples, callset id -> row)."""
    ids = [cohort.sample_ids[i] for i in perm]
    g = g_ref[np.ix_(perm, perm)].copy()
    coords = [(cid, c_ref[i, 0] + coord_shift, -c_ref[i, 1])
              for cid, i in zip(ids, perm)]
    return g, coords, {cid: r for r, cid in enumerate(ids)}


@pytest.mark.parametrize("fault", ["none", "one_count", "bf16_gramian",
                                   "coordinate"])
@pytest.mark.parametrize("order", ["identity", "shuffled"])
def test_numbers_equal_the_old_float64_comparison(answers, fault, order):
    cohort, g_ref, c_ref = answers
    n = cohort.n_samples
    perm = (np.arange(n) if order == "identity"
            else np.random.default_rng(5).permutation(n))
    results = []
    for i in range(3):
        g, coords, rows = _answer(
            cohort, g_ref, c_ref, perm,
            coord_shift=3e-6 if fault == "coordinate" and i == 1 else 0.0)
        if fault == "one_count" and i == 2:
            g[n - 1, 7] += 1.0
        if fault == "bf16_gramian" and i == 0:
            g = reference.control_gramian(g)
        results.append((g, coords, rows))
    checks = run.compare(cohort, results, 2, LIMITS, (g_ref, c_ref))
    g_err, coord_err = _old_compare(cohort, results, g_ref, c_ref)
    assert checks["g_err"]["value"] == g_err
    assert checks["coord_err"]["value"] == coord_err
    assert checks["analyses_compared"]["value"] == 3
    assert run.checks_pass(checks) is (fault == "none")
    if fault == "one_count":
        assert g_err == 1.0


def test_nan_reads_as_not_correct(answers):
    """The old ``max(err, nan)`` kept err: a NaN in G or in the
    coordinates read as no difference at all."""
    cohort, g_ref, c_ref = answers
    n = cohort.n_samples
    good = _answer(cohort, g_ref, c_ref, np.arange(n))
    g = good[0].copy()
    g[3, 4] = np.nan
    coords = [(cid, np.nan if i == 0 else a, b)
              for i, (cid, a, b) in enumerate(good[1])]
    checks = run.compare(cohort, [good, (g, coords, good[2])], 2, LIMITS,
                         (g_ref, c_ref))
    assert checks["g_err"]["value"] == float("inf")
    assert checks["coord_err"]["value"] == float("inf")
    assert not run.checks_pass(checks)


def test_permuted_is_the_reference_itself_in_its_own_order():
    g = np.arange(16, dtype=np.float32).reshape(4, 4)
    assert reference.permuted(g, np.arange(4)) is g
    np.testing.assert_array_equal(reference.permuted(g, np.array([2, 0, 3, 1])),
                                  g[[2, 0, 3, 1]][:, [2, 0, 3, 1]])


@pytest.mark.parametrize("n,want", [
    (2504, 685),  # kgp3_chr20: every analysis of a window
    (3202, 418),  # nygc_chr20_rare: every analysis of a window
    (8192, 64),
    (23170, 8),
    (49960, 2),  # the first and the last
    (10**6, 2),
])
def test_keep_count_follows_the_hosts_budget(n, want):
    assert run.keep_count(n) == want
    assert want * 4 * n * n <= max(run.HOST_KEEP_BYTES, 2 * 4 * n * n)


class _G:
    """A device G stand-in that records its copies to the host."""

    copied = []

    def __init__(self, i):
        self.i = i

    def copy_to_host_async(self):
        _G.copied.append(self.i)

    def __array__(self, dtype=None, copy=None):
        return np.full((2, 2), float(self.i), dtype=dtype or np.float32)


@pytest.mark.parametrize("room", [2, 3, 5, 9, 10, 1000])
def test_window_keeps_the_first_answers_and_the_last(
        tmp_path, monkeypatch, capsys, room):
    """Past the budget only the last is kept, and a G that is not kept is
    never copied to the host."""
    bh.stub_chip(monkeypatch)
    _G.copied = []
    made, seen, clock = [], [], [0.0]

    def analysis(cohort_dir, refs, flags):
        clock[0] += 1.0  # each analysis takes a second
        made.append(len(made))
        return _G(made[-1]), [], {}

    def compare(cohort, results, num_pc, limits, answers=None,
                devices=None):
        seen.extend(int(np.asarray(g)[0, 0]) for g, _, _ in results)
        return {"g_err": {"value": 0.0, "limit": 0.0},
                "coord_err": {"value": 0.0, "limit": 1.0},
                "analyses_compared": {"value": len(results), "limit": 1}}

    monkeypatch.setattr(run, "analysis", analysis)
    monkeypatch.setattr(run, "compare", compare)
    monkeypatch.setattr(run, "keep_count", lambda n: room)
    monkeypatch.setattr(run.time, "perf_counter", lambda: clock[0])
    code = run.main(["--workload", "nygc_chr20_rare.sparse", "--seed",
                     str(2**32 + 9), "--seconds", "10", "--trace", "0"],
                    root=bh.tiny_root(tmp_path))
    out, _ = capsys.readouterr()
    assert code == 0
    window = made[1:]  # made[0] is set-up's warm analysis
    want = window[:room - 1] + ([window[-1]] if len(window) >= room else [])
    assert seen == want
    assert _G.copied == window[:room - 1]
    line = bh.last_json_line(out)
    assert line["attempted"] == len(window) == 10
    assert line["metrics"]["analysis_s"]["value"] == 1.0


def test_window_compares_every_analysis_within_the_budget(
        tmp_path, monkeypatch, capsys):
    bh.stub_chip(monkeypatch)
    code = run.main(["--workload", "nygc_chr20_rare.sparse", "--seed",
                     str(2**32 + 9), "--seconds", "1.0", "--trace", "0"],
                    root=bh.tiny_root(tmp_path))
    out, err = capsys.readouterr()
    assert code == 0
    line = bh.last_json_line(out)
    assert line["correct"] is True
    assert line["checks"]["analyses_compared"]["value"] == line["attempted"]
    assert f"window: {line['attempted']} analyses in" in err


class _Device:
    def __init__(self, stats):
        self.stats = stats

    def memory_stats(self):
        return self.stats


def test_memory_peak_is_the_largest_over_the_cells_devices():
    devices = [_Device({"peak_bytes_in_use": 5}),
               _Device({"peak_bytes_in_use": 9, "bytes_in_use": 1}),
               _Device({"peak_bytes_in_use": 7}), _Device(None)]
    assert run.peak_memory(devices) == 9
    assert run.peak_memory(devices[:1]) == 5
    assert run.peak_memory([_Device(None)]) == 0
