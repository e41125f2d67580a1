"""bench.py's host-side legs still emit their records, on the CPU mesh.

``bench.py`` refuses to run without a TPU, so its CPU CI legs are gone;
these tests keep what those legs checked — each record's schema, the
serving tier's bit-identity, the 2-process pod-sparse protocol and the
telemetry artifacts ``scripts/validate_trace.py`` accepts — by calling
the leg functions with the TPU gate stubbed out. The numbers are CPU
numbers of tiny runs and are never reported anywhere.
"""

import importlib.util
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import bench  # noqa: E402


def _load_validator():
    spec = importlib.util.spec_from_file_location(
        "validate_trace", os.path.join(ROOT, "scripts", "validate_trace.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


validate = _load_validator()

CPU = {"platform": "cpu", "kind": "cpu", "count": 1}


@pytest.fixture
def run_leg(monkeypatch, capsys):
    """Run one leg with the TPU gate stubbed; → its JSON record."""
    monkeypatch.setattr(bench, "_require_tpu", lambda: dict(CPU))

    def run(leg, **env):
        for key, value in env.items():
            monkeypatch.setenv(key, str(value))
        capsys.readouterr()
        leg()
        lines = capsys.readouterr().out.strip().splitlines()
        return json.loads(lines[-1])

    return run


def test_bench_refuses_without_a_tpu():
    with pytest.raises(SystemExit) as e:
        bench._require_tpu()
    assert e.value.code == 2


def test_scale_out_sweep_record_and_pod_protocol(run_leg):
    d = run_leg(
        bench.scale_out_sweep,
        BENCH_SCALE_NS="512,1024", BENCH_SCALE_V=256,
        BENCH_SCALE_POD_N=512, BENCH_SCALE_POD_V=256,
        BENCH_SCALE_SKETCH_NS=65536, BENCH_SCALE_SKETCH_V=512,
    )
    assert d["metric"] == "sparse_gramian_nnz_per_sec", d
    assert d["provenance"]["mesh"], d
    assert [s["n"] for s in d["sweep"]] == [512, 1024], d
    pod = d["pod"]
    assert pod.get("nnz_per_sec"), pod
    assert pod["processes"] == 2 and pod["mesh"], pod
    sk = d["sketch"]
    assert sk["metric"] == "sketch_samples_per_sec", sk
    rec = sk["sweep"][0]
    assert rec["n"] == 65536 and rec["samples_per_sec"] > 0, rec
    assert rec["sketch_host_bytes"] < rec["exact_host_g_bytes"], rec


def test_cold_start_record_and_telemetry(run_leg, tmp_path):
    paths = {
        "trace": tmp_path / "cold.trace.json",
        "metrics": tmp_path / "cold.metrics.prom",
        "manifest": tmp_path / "cold.manifest.json",
    }
    d = run_leg(
        bench.cold_start_bench,
        BENCH_COLD_RTT=0.02, BENCH_TRACE_OUT=paths["trace"],
        BENCH_METRICS_OUT=paths["metrics"],
        BENCH_MANIFEST_OUT=paths["manifest"],
    )
    assert d["metric"] == "cold_ingest_seconds", d
    assert d["cold_to_warm_ratio"] > 0, d
    assert d["provenance"]["transport"] == "http-loopback", d
    assert validate.validate_trace(str(paths["trace"])) == []
    assert validate.validate_metrics(str(paths["metrics"])) == []
    assert validate.validate_manifest(str(paths["manifest"])) == []


def test_serving_record_bit_identity_and_telemetry(run_leg, tmp_path):
    trace = tmp_path / "serve.trace.json"
    prom = tmp_path / "serve.metrics.prom"
    rec = run_leg(
        bench.serving_bench,
        BENCH_SERVE_JOBS=6, BENCH_TRACE_OUT=trace, BENCH_METRICS_OUT=prom,
    )
    for key in (
        "serial_jobs_per_sec", "gang_jobs_per_sec", "cold_seconds",
        "delta_seconds", "provenance",
    ):
        assert key in rec, f"missing {key}"
    assert rec["bit_identical"] is True
    # The scrape-overhead ratio is a timing: on a loaded test host it is
    # only checked to be measured, not bounded.
    assert rec["metrics_scrape_p50_ms"] > 0, rec
    assert rec["metrics_scrape_p99_ms"] >= rec["metrics_scrape_p50_ms"]
    assert rec["scrape_overhead_ratio"] > 0, rec
    assert validate.validate_trace(str(trace)) == []
    assert validate.validate_metrics(str(prom)) == []
    names = {
        e.get("name") for e in json.loads(trace.read_text())["traceEvents"]
    }
    assert "job.gang" in names and "job.delta" in names, names
    text = prom.read_text()
    assert "serving_delta_jobs_total" in text
    assert "serving_gang_size_bucket" in text


def test_pairhmm_record(run_leg):
    d = run_leg(bench.pairhmm_bench, BENCH_PAIRHMM_READS=256)
    assert d["metric"] == "pairhmm_pairs_per_sec", d
    assert d["value"] > 0 and d["device"] == CPU, d
