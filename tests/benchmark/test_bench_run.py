"""run.py end to end on the CPU at a tiny size, the chip check stubbed:
the result line's schema, the per-layer line, the exit without a TPU, and
cells, mixes and metrics found by name."""

import json
import os

import pytest

import bench_helpers as bh
from benchmark import run

SEED = str(2**33 + 5)


def _run(root, workload, trace, capsys, seconds="0.3"):
    code = run.main(["--workload", workload, "--seed", SEED, "--seconds",
                     seconds, "--trace", str(trace)], root=root)
    out, err = capsys.readouterr()
    return code, out, err


@pytest.mark.parametrize("workload", ["kgp3_chr20.auto",
                                      "nygc_chr20_rare.sparse",
                                      "nygc_chr20_rare_full.auto"])
def test_cell_runs_end_to_end_and_prints_the_last_line(
        tmp_path, monkeypatch, capsys, workload):
    bh.stub_chip(monkeypatch)
    code, out, err = _run(bh.tiny_root(tmp_path), workload, 0, capsys)
    assert code == 0
    line = bh.last_json_line(out)
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(line)[-1] == "checks"
    assert line["correct"] is True
    assert line["failed"] == 0 and line["attempted"] >= 1
    assert set(line["metrics"]) == {"analysis_s", "setup_s"}
    for m in line["metrics"].values():
        assert m["value"] > 0 and m["unit"] == "s"
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(
        line["device"])
    checks = line["checks"]
    assert checks["g_err"]["value"] == 0.0
    assert checks["coord_err"]["value"] <= checks["coord_err"]["limit"]
    # The numbers compared, each beside its limit, end stderr.
    tail = err.strip().splitlines()[-len(checks):]
    assert [ln.split()[1].rstrip(":") for ln in tail] == list(checks)
    assert all(" limit " in ln for ln in tail)


def test_traced_run_reports_per_layer_metrics(tmp_path, monkeypatch,
                                              capsys):
    bh.stub_chip(monkeypatch)
    code, out, err = _run(bh.tiny_root(tmp_path), "kgp3_chr20.auto", 1,
                          capsys)
    assert code == 0
    line = bh.last_json_line(out)
    assert line["correct"] is True
    spec = json.load(open(os.path.join(bh.REPO, "BENCHMARK.json")))
    names = {m["name"] for m in spec["per_layer"]}
    # The CPU has no device plane: the device readers find nothing and
    # their metrics are left out, never reported as 0.
    assert set(line["metrics"]) == {"compiles_in_window", "ingest_wall_s"}
    assert set(line["metrics"]) <= names
    assert line["metrics"]["compiles_in_window"]["value"] == 0.0
    assert line["metrics"]["ingest_wall_s"]["value"] > 0.0
    assert line["device"]["window_s"] > 0.0
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
    assert "windows by route and scatter path" in err


def test_no_tpu_exits_nonzero_and_prints_no_result(tmp_path, monkeypatch,
                                                   capsys):
    monkeypatch.setattr(run, "use_compile_cache", lambda root: None)
    code, out, err = _run(bh.tiny_root(tmp_path), "kgp3_chr20.auto", 0,
                          capsys)
    assert code != 0
    assert out == ""
    assert "no TPU" in err


def test_added_config_mix_metric_and_cell_are_found_by_name(
        tmp_path, monkeypatch, capsys):
    """A later PR adds a cell with only new files and new entries."""
    bh.stub_chip(monkeypatch)
    root = bh.tiny_root(tmp_path)
    bench = os.path.join(root, "benchmark")
    cfg = bh.load_config("nygc_chr20_rare")
    cfg["name"] = "nygc_twin"
    cfg["cohort"] = bh.tiny_cohort(cfg, 2500)
    with open(os.path.join(bench, "configs", "nygc_twin.json"), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(bench, "traffic", "stream.json"), "w") as f:
        json.dump({"flags": ["--pca-mode", "stream"]}, f)
    with open(os.path.join(bench, "metrics", "analyses_seen.py"), "w") as f:
        f.write('LAYER = "driver"\nUNIT = "count"\nMOVES = "analysis_s"\n'
                'SOURCE = "program_counter"\n\n\n'
                "def read(obs):\n    return float(obs.analyses)\n")
    spec = json.load(open(os.path.join(root, "BENCHMARK.json")))
    spec["configs"].append({
        "name": "nygc_twin", "source": "https://example.org/twin",
        "file": "benchmark/configs/nygc_twin.json", "reduced": ["sites"],
        "why": "a test twin"})
    spec["workloads"].append({
        "name": "nygc_twin.stream", "config": "nygc_twin",
        "traffic": "stream", "chips": 1, "why": "a test cell"})
    spec["per_layer"].append({
        "name": "analyses_seen", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "driver",
        "moves": "analysis_s", "workloads": ["nygc_twin.stream"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)

    code, out, _ = _run(root, "nygc_twin.stream", 1, capsys)
    assert code == 0
    line = bh.last_json_line(out)
    assert line["correct"] is True
    assert line["metrics"]["analyses_seen"]["value"] == line["attempted"]
    # The existing cells do not report the new metric.
    assert "analyses_seen" not in {
        m["name"] for m in run.resolve_cell(root, "kgp3_chr20.auto").per_layer
    }
