"""The reader of the CSR sidecar's open per analysis (``sidecar_open_s``):
on hand-made spans with answers worked by hand, and in a traced run of
the rare cohort on the CPU."""

import json
import os

import pytest

import bench_helpers as bh
from benchmark import run


def _read(spans, analyses=2):
    reader = run.load_module(bh.REPO, "metrics", "sidecar_open_s")
    return reader.read(run.Observed(analyses=analyses, compiles=0, work={},
                                    peaks={}, spans=spans))


def test_sums_the_opens_over_the_analyses():
    spans = [
        ("ingest.sidecar_open", 1.0, 1.25, 7),
        ("ingest.fetch", 0.5, 2.0, 7),          # the span it sits in
        ("ingest.fetch", 0.6, 2.0, 8),          # a fetch waiting on it
        ("ingest.sidecar_open", 5.0, 5.5, 9),   # the next analysis's
    ]
    assert _read(spans) == pytest.approx((0.25 + 0.5) / 2)
    assert _read(spans, analyses=3) == pytest.approx(0.75 / 3)


@pytest.mark.parametrize("spans,analyses", [
    ([], 2),                                          # no spans at all
    ([("ingest.fetch", 0.0, 1.0, 1)], 2),             # no open span
    ([("ingest.sidecar_open", 0.0, 1.0, 1)], 0),      # no analysis
])
def test_finds_nothing_to_read(spans, analyses):
    assert _read(spans, analyses) is None


def test_listed_for_the_rare_cells_with_its_reader():
    spec = json.load(open(os.path.join(bh.REPO, "BENCHMARK.json")))
    (entry,) = [m for m in spec["per_layer"] if m["name"] == "sidecar_open_s"]
    assert entry["workloads"] == ["nygc_chr20_rare.sparse",
                                  "nygc_chr20_rare_full.auto"]
    assert (entry["layer"], entry["moves"], entry["source"]) == (
        "host ingest", "analysis_s", "program_span")


def test_traced_rare_run_reports_the_open(tmp_path, monkeypatch, capsys):
    """Every analysis opens a fresh source, so the CPU traced run reads an
    open in each, and no more than the ingest it sits in."""
    bh.stub_chip(monkeypatch)
    code = run.main(["--workload", "nygc_chr20_rare.sparse", "--seed",
                     str(2**33 + 29), "--seconds", "0.3", "--trace", "1"],
                    root=bh.tiny_root(tmp_path))
    out, _ = capsys.readouterr()
    assert code == 0
    line = bh.last_json_line(out)
    assert line["correct"] is True
    metrics = line["metrics"]
    assert 0.0 < metrics["sidecar_open_s"]["value"]
    assert metrics["sidecar_open_s"]["value"] <= (
        metrics["ingest_wall_s"]["value"])
    assert metrics["sidecar_open_s"]["unit"] == "s"
