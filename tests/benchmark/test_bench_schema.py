"""BENCHMARK.json against the benchmark's contract, and each entry against
the files the harness finds by its name."""

import json
import os
import re

import pytest

import bench_helpers as bh
from benchmark import run

SPEC_PATH = os.path.join(bh.REPO, "BENCHMARK.json")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_./-]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def spec():
    with open(SPEC_PATH) as f:
        return json.load(f)


def _line(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level(spec):
    assert os.path.getsize(SPEC_PATH) <= 64 * 1024
    assert list(spec) == ["command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"]
    assert spec["command"] == ["python3", "benchmark/run.py"]
    assert 1 <= len(spec["paths"]) <= 16
    for path in spec["paths"]:
        assert PATH.match(path) and ".." not in path
        assert os.path.isdir(os.path.join(bh.REPO, path))
    assert isinstance(spec["run_seconds"], int)
    assert 1 <= spec["run_seconds"] <= 51
    # A full check of 24 cells fits the driver's 43,200 s.
    cells = 24
    total = ((2 + 14 * cells) * (spec["run_seconds"] + 60)
             + cells * 2 * 90 + 1200)
    assert total <= 43200


def test_names_units_and_text(spec):
    entries = (spec["configs"] + spec["workloads"] + spec["end_to_end"]
               + spec["per_layer"])
    for e in entries:
        assert NAME.match(e["name"]), e["name"]
    for group in ("configs", "workloads"):
        names = [e["name"] for e in spec[group]]
        assert len(names) == len(set(names))
    metrics = [m["name"] for m in spec["end_to_end"] + spec["per_layer"]]
    assert len(metrics) == len(set(metrics))
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for e in spec["configs"] + spec["workloads"]:
        assert _line(e["why"])
    for c in spec["configs"]:
        assert _line(c["source"]) and c["source"].startswith("https://")
        assert len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])


def test_configs_and_cells(spec):
    used = {w["config"] for w in spec["workloads"]}
    files = [c["file"] for c in spec["configs"]]
    assert len(files) == len(set(files))
    for c in spec["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["name"] in used
        assert c["file"].startswith(tuple(p + "/" for p in spec["paths"]))
        with open(os.path.join(bh.REPO, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["name"] == c["name"] and cfg["source"] == c["source"]
        assert cfg["reduced"] == c["reduced"]
        # A reduced key names a cut of scale and says what it was.
        for key in c["reduced"]:
            assert key in cfg["published"] and key in cfg["cohort"]
        assert os.path.exists(os.path.join(
            bh.BENCH, "cohorts", cfg["cohort"]["generator"] + ".py"))
        assert set(cfg["limits"]) == {"g_err", "coord_err"}
    pairs = [(w["config"], w["traffic"]) for w in spec["workloads"]]
    assert len(pairs) == len(set(pairs))
    four = sum(w["chips"] == 4 for w in spec["workloads"])
    assert four <= max(1, len(spec["workloads"]) // 2)
    for w in spec["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4)
        assert os.path.exists(os.path.join(bh.BENCH, "traffic",
                                           w["traffic"] + ".json"))


def test_metrics_and_their_readers(spec):
    e2e = {m["name"] for m in spec["end_to_end"]}
    cells = {w["name"] for w in spec["workloads"]}
    assert "setup_s" in e2e
    for m in spec["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    layers = {}
    for m in spec["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in e2e and _line(m["layer"])
        assert set(m.get("workloads", cells)) <= cells
        reader = run.load_module(bh.REPO, "metrics", m["name"])
        assert (reader.LAYER, reader.UNIT, reader.MOVES, reader.SOURCE) == (
            m["layer"], m["unit"], m["moves"], m["source"])
        assert callable(reader.read)
        layers.setdefault(m["layer"], set()).add(m["name"])
    assert set(layers) == {"driver", "host ingest", "Gramian", "finish",
                           "device"}


@pytest.mark.parametrize("workload", ["kgp3_chr20.auto",
                                      "nygc_chr20_rare.sparse",
                                      "nygc_chr20_rare_full.auto"])
def test_every_cell_reports_what_the_contract_asks(spec, workload):
    cell = run.resolve_cell(bh.REPO, workload)
    names = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert cell.per_layer
    assert cell.chips == 1
