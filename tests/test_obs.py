"""Unified telemetry layer: tracer, registry, manifest, validation.

Pins the contracts the observability subsystem ships on:

- registry counter/histogram semantics under concurrent writers (the
  transports feed them from shard-parallel ingest threads);
- Chrome-trace JSON schema round-trip — every emitted trace must pass
  ``scripts/validate_trace.py`` (the same check CI applies), i.e. load
  in Perfetto;
- ``StageTimer`` thread-safety (thread-local span stacks, locked
  accumulation) and its unchanged report block;
- ``IoStats`` parity: the exact ``report()`` format the reference pins
  (VariantsRDD.scala:168-180) AND the counters' visibility through the
  registry collector, including after the owning source is dropped;
- manifest emission from a real (tiny, CPU) CLI pipeline run with
  ``--trace-out/--metrics-out/--manifest-out`` — the acceptance shape:
  stage timings, the parity counters, and an RPC latency histogram, all
  schema-valid.
"""

from __future__ import annotations

import importlib.util
import json
import os
import threading

import pytest

from spark_examples_tpu import obs
from spark_examples_tpu.native import native_available
from spark_examples_tpu.obs.metrics import MetricsRegistry
from spark_examples_tpu.obs.session import TelemetrySession
from spark_examples_tpu.obs.tracer import SpanTracer
from spark_examples_tpu.utils.stats import IoStats
from spark_examples_tpu.utils.tracing import StageTimer

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_validator():
    spec = importlib.util.spec_from_file_location(
        "validate_trace",
        os.path.join(_REPO_ROOT, "scripts", "validate_trace.py"),
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


validate = _load_validator()


class TestMetricsRegistry:
    def test_counter_semantics_under_threads(self):
        reg = MetricsRegistry()
        c = reg.counter("req_total", "requests")
        n_threads, per_thread = 8, 2000

        def work():
            for _ in range(per_thread):
                c.inc()

        threads = [threading.Thread(target=work) for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert c.value == n_threads * per_thread

    def test_histogram_semantics_under_threads(self):
        reg = MetricsRegistry()
        h = reg.histogram("lat_seconds", buckets=(0.1, 1.0, 10.0))
        values = [0.05, 0.5, 5.0, 50.0]

        def work():
            for v in values:
                h.observe(v)

        threads = [threading.Thread(target=work) for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert h.count == 8 * len(values)
        assert h.sum == pytest.approx(8 * sum(values))
        s = h.summary()
        assert s["count"] == 32
        assert s["min"] == pytest.approx(0.05)
        assert s["max"] == pytest.approx(50.0)
        assert 0.0 < s["p50"] <= 10.0

    def test_counters_only_go_up(self):
        c = MetricsRegistry().counter("c_total")
        with pytest.raises(ValueError):
            c.inc(-1)

    def test_kind_conflict_is_loud(self):
        reg = MetricsRegistry()
        reg.counter("x_total")
        with pytest.raises(ValueError):
            reg.gauge("x_total")

    def test_labels_partition_series(self):
        reg = MetricsRegistry()
        c = reg.counter("rpc_total")
        c.labels(transport="http").inc(2)
        c.labels(transport="grpc").inc(3)
        snap = reg.snapshot()
        assert snap["counters"]['rpc_total{transport="http"}'] == 2
        assert snap["counters"]['rpc_total{transport="grpc"}'] == 3

    def test_prometheus_exposition_is_schema_valid(self, tmp_path):
        reg = MetricsRegistry()
        reg.counter("a_total", "help text").inc(5)
        reg.gauge("g_now").set(-2.5)
        h = reg.histogram("h_seconds", "latency")
        h.labels(method="x").observe(0.3)
        path = str(tmp_path / "m.prom")
        reg.write_prometheus(path)
        assert validate.validate_metrics(path) == []
        text = open(path).read()
        assert "# TYPE a_total counter" in text
        assert 'h_seconds_bucket{method="x",le="+Inf"} 1' in text

    def test_jsonl_sink_appends_snapshots(self, tmp_path):
        reg = MetricsRegistry()
        reg.counter("a_total").inc()
        path = str(tmp_path / "m.jsonl")
        reg.write_jsonl(path)
        reg.counter("a_total").inc()
        reg.write_jsonl(path)
        lines = [json.loads(l) for l in open(path)]
        assert len(lines) == 2
        assert lines[0]["counters"]["a_total"] == 1
        assert lines[1]["counters"]["a_total"] == 2


class TestSpanTracer:
    def test_trace_schema_roundtrip_under_threads(self, tmp_path):
        tracer = SpanTracer()

        def work(i):
            with tracer.span("outer", worker=i):
                with tracer.span("inner"):
                    tracer.instant("mark", i=i)

        threads = [
            threading.Thread(target=work, args=(i,)) for i in range(6)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        path = str(tmp_path / "t.trace.json")
        tracer.write(path)
        assert validate.validate_trace(path) == []
        doc = json.load(open(path))
        names = [e["name"] for e in doc["traceEvents"]]
        assert names.count("outer") == 6
        assert names.count("inner") == 6
        assert names.count("mark") == 6
        # Aggregates: each name accumulated once per span.
        assert tracer.stage_counts()["outer"] == 6
        assert tracer.stage_seconds()["inner"] >= 0.0

    def test_span_stack_is_thread_local(self):
        tracer = SpanTracer()
        seen = {}
        gate = threading.Barrier(2)

        def a():
            with tracer.span("a"):
                gate.wait()
                seen["a"] = tracer.current_span()
                gate.wait()

        def b():
            with tracer.span("b"):
                gate.wait()
                seen["b"] = tracer.current_span()
                gate.wait()

        ta, tb = threading.Thread(target=a), threading.Thread(target=b)
        ta.start(), tb.start()
        ta.join(), tb.join()
        assert seen == {"a": "a", "b": "b"}

    def test_event_cap_counts_drops(self, tmp_path):
        tracer = SpanTracer(max_events=3)
        for i in range(10):
            tracer.instant(f"e{i}")
        doc = tracer.to_chrome()
        dropped = [
            e
            for e in doc["traceEvents"]
            if e["name"] == "tracer_events_dropped"
        ]
        assert dropped and dropped[0]["args"]["dropped"] == 7

    def test_ambient_helpers_noop_without_session(self):
        # No session active: module helpers must not record anywhere.
        assert not obs.collection_active()
        with obs.span("ghost"):
            obs.instant("ghost_mark")
        # A fresh session must not see pre-session ghosts.
        with TelemetrySession() as s:
            assert obs.collection_active()
        assert "ghost" not in s.tracer.stage_seconds()


class TestStageTimer:
    def test_report_format_unchanged(self):
        timer = StageTimer()
        with timer.stage("ingest"):
            timer.note("a note")
        with timer.stage("pca"):
            pass
        timer.note("orphan note")
        report = timer.report()
        lines = report.splitlines()
        assert lines[0] == "Stage wall-clock"
        assert lines[1] == "----------------"
        assert lines[2].startswith("ingest: ") and "%" in lines[2]
        assert lines[3] == "  a note"
        assert lines[4].startswith("pca: ")
        assert lines[5] == "orphan note"
        assert lines[6].startswith("total: ")

    def test_concurrent_stages_accumulate_safely(self):
        timer = StageTimer()
        n_threads, per_thread = 8, 50

        def work(i):
            for _ in range(per_thread):
                with timer.stage("shared"):
                    pass
                with timer.stage(f"own-{i}"):
                    timer.note(f"note-{i}")

        threads = [
            threading.Thread(target=work, args=(i,)) for i in range(n_threads)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert timer.seconds["shared"] >= 0.0
        assert len(timer.seconds) == 1 + n_threads
        # Notes filed under the stage open on THEIR thread, never a
        # sibling's (the thread-local stack contract).
        for i in range(n_threads):
            assert timer.notes[f"own-{i}"] == [f"note-{i}"] * per_thread

    def test_stages_mirror_into_active_session(self):
        with TelemetrySession() as s:
            timer = StageTimer()
            with timer.stage("mirrored"):
                pass
        assert "mirrored" in s.tracer.stage_seconds()


class TestIoStatsRegistryBacking:
    def test_report_block_format_exact(self):
        stats = IoStats()
        stats.add(partitions=2, variants_read=7, reference_bases=100)
        assert stats.report() == (
            "Variants API stats\n"
            "------------------\n"
            "# of partitions: 2\n"
            "# of reference bases requested: 100\n"
            "# of API requests: 0\n"
            "# of unsuccessful responses: 0\n"
            "# of IO exceptions: 0\n"
            "# of variants read: 7\n"
            "# of reads read: 0\n"
        )

    def test_live_instance_visible_through_collector(self):
        reg = MetricsRegistry()
        before = reg.snapshot()["counters"][
            "genomics_io_variants_read_total"
        ]
        stats = IoStats()
        stats.add(variants_read=11)
        after = reg.snapshot()["counters"][
            "genomics_io_variants_read_total"
        ]
        assert after - before == 11
        del stats  # keep referenced until the second snapshot

    def test_untracked_merge_view_is_invisible_to_collector(self):
        # allreduce_host_stats builds a merged VIEW of per-source
        # counters; tracking it would double-count multi-host manifests.
        reg = MetricsRegistry()
        before = reg.snapshot()["counters"][
            "genomics_io_variants_read_total"
        ]
        src = IoStats()
        src.add(variants_read=9)
        merged = IoStats.untracked()
        merged.merge(src)
        after = reg.snapshot()["counters"][
            "genomics_io_variants_read_total"
        ]
        assert after - before == 9  # src only, never the merged copy
        del merged
        import gc

        gc.collect()
        final = reg.snapshot()["counters"][
            "genomics_io_variants_read_total"
        ]
        assert final - before == 9  # untracked never retires either
        del src

    def test_dropped_instance_counts_are_retired_not_lost(self):
        reg = MetricsRegistry()
        stats = IoStats()
        stats.add(requests=5)
        del stats
        import gc

        gc.collect()
        # The retired totals keep contributing after GC — the end-of-run
        # manifest flush happens after the driver drops its source.
        assert (
            reg.snapshot()["counters"]["genomics_io_requests_total"] >= 5
        )


class TestValidator:
    def test_malformed_trace_fails(self, tmp_path):
        path = tmp_path / "bad.trace.json"
        path.write_text(
            json.dumps(
                {"traceEvents": [{"name": "x", "ph": "X", "pid": 1}]}
            )
        )
        errs = validate.validate_trace(str(path))
        assert any("ts" in e for e in errs)
        assert any("dur" in e for e in errs)

    def test_malformed_metrics_fails(self, tmp_path):
        path = tmp_path / "bad.prom"
        path.write_text("this is { not prometheus\n")
        assert validate.validate_metrics(str(path)) != []

    def test_manifest_missing_keys_fails(self, tmp_path):
        path = tmp_path / "bad.manifest.json"
        path.write_text(json.dumps({"schema": "nope"}))
        errs = validate.validate_manifest(str(path))
        assert any("stages" in e for e in errs)

    def test_cli_entry_point_exit_codes(self, tmp_path, capsys):
        good = tmp_path / "ok.trace.json"
        t = SpanTracer()
        t.instant("x")
        t.write(str(good))
        assert validate.main(["--trace", str(good)]) == 0
        bad = tmp_path / "bad.trace.json"
        bad.write_text("{}")
        assert validate.main(["--trace", str(bad)]) == 1


class TestTelemetrySession:
    def test_artifacts_written_on_failure_path(self, tmp_path):
        trace = str(tmp_path / "f.trace.json")
        manifest = str(tmp_path / "f.manifest.json")
        with pytest.raises(RuntimeError):
            with TelemetrySession(
                trace_out=trace, manifest_out=manifest, command="boom"
            ):
                with obs.span("doomed"):
                    raise RuntimeError("simulated crash")
        assert validate.validate_trace(trace) == []
        mf = json.load(open(manifest))
        assert mf["outcome"] == "error"
        assert "doomed" in mf["stages"]

    def test_flush_telemetry_midrun(self, tmp_path):
        # The watchdog's fail-stop path: flush BEFORE os._exit.
        trace = str(tmp_path / "w.trace.json")
        with TelemetrySession(trace_out=trace):
            obs.instant("collective_watchdog_fired", phase="merge")
            obs.flush_telemetry(reason="test")
            assert validate.validate_trace(trace) == []
            doc = json.load(open(trace))
            names = [e["name"] for e in doc["traceEvents"]]
            assert "collective_watchdog_fired" in names

    def test_rpc_timer_feeds_session_registry(self):
        with TelemetrySession() as s:
            with obs.rpc_timer("test", "Op"):
                pass
            with pytest.raises(IOError):
                with obs.rpc_timer("test", "Op"):
                    raise IOError("boom")
        snap = s.registry.snapshot()
        key = 'genomics_rpc_latency_seconds{method="Op",transport="test"}'
        assert snap["histograms"][key]["count"] == 2
        err_key = 'genomics_rpc_errors_total{method="Op",transport="test"}'
        assert snap["counters"][err_key] == 1

    def test_xla_cost_is_accepted_off_and_refused_on(self):
        with TelemetrySession(xla_cost=False) as s:
            with obs.span("stage"):
                pass
        names = [e["name"] for e in s.tracer.to_chrome()["traceEvents"]]
        assert "stage" in names
        with pytest.raises(ValueError, match="xla_cost"):
            TelemetrySession(xla_cost=True)


class TestPipelineManifestEmission:
    """The acceptance shape: a CPU-only CLI pca run with all three
    outputs produces Perfetto-loadable trace JSON, a valid Prometheus
    dump, and a manifest with stage timings + parity counters + an RPC
    latency histogram."""

    @pytest.fixture(scope="class")
    def run_artifacts(self, tmp_path_factory):
        from spark_examples_tpu.cli.main import main

        tmp_path = tmp_path_factory.mktemp("obs_cli")
        paths = {
            "trace": str(tmp_path / "run.trace.json"),
            "metrics": str(tmp_path / "run.metrics.prom"),
            "manifest": str(tmp_path / "run.manifest.json"),
        }
        old = os.environ.get("SPARK_EXAMPLES_TPU_COMPILE_CACHE")
        os.environ["SPARK_EXAMPLES_TPU_COMPILE_CACHE"] = "0"
        try:
            rc = main(
                [
                    "pca",
                    "--fixture-samples",
                    "8",
                    "--fixture-variants",
                    "64",
                    "--output-path",
                    str(tmp_path / "out"),
                    "--trace-out",
                    paths["trace"],
                    "--metrics-out",
                    paths["metrics"],
                    "--manifest-out",
                    paths["manifest"],
                ]
            )
        finally:
            if old is None:
                os.environ.pop("SPARK_EXAMPLES_TPU_COMPILE_CACHE", None)
            else:
                os.environ["SPARK_EXAMPLES_TPU_COMPILE_CACHE"] = old
        assert rc == 0
        return paths

    def test_all_artifacts_schema_valid(self, run_artifacts):
        assert validate.validate_trace(run_artifacts["trace"]) == []
        assert validate.validate_metrics(run_artifacts["metrics"]) == []
        assert (
            validate.validate_manifest(run_artifacts["manifest"]) == []
        )

    def test_manifest_has_stage_timings(self, run_artifacts):
        mf = json.load(open(run_artifacts["manifest"]))
        for stage in ("run", "ingest+gramian", "pca", "emit"):
            assert stage in mf["stages"], mf["stages"].keys()
            assert mf["stages"][stage]["seconds"] >= 0.0
        assert mf["command"] == "pca"
        assert mf["config"]["fixture_samples"] == 8
        assert mf["environment"]["jax"]["backend"] == "cpu"

    def test_manifest_has_parity_counters(self, run_artifacts):
        mf = json.load(open(run_artifacts["manifest"]))
        for field in (
            "partitions",
            "reference_bases",
            "requests",
            "unsuccessful_responses",
            "io_exceptions",
            "variants_read",
            "reads_read",
        ):
            assert f"genomics_io_{field}_total" in mf["counters"]
        # The run read its 64 fixture variants (process-cumulative
        # counter: other tests may have contributed more).
        assert mf["counters"]["genomics_io_variants_read_total"] >= 64
        # The driver-merged job-end totals are exact per run (gauges in
        # the session-fresh registry, set at report_io_stats time).
        assert mf["gauges"]["genomics_io_merged_variants_read"] == 64

    def test_manifest_has_rpc_latency_histogram(self, run_artifacts):
        mf = json.load(open(run_artifacts["manifest"]))
        rpc = {
            k: v
            for k, v in mf["histograms"].items()
            if k.startswith("genomics_rpc_latency_seconds")
        }
        assert rpc, list(mf["histograms"])
        assert any(v["count"] >= 1 for v in rpc.values())

    def test_trace_has_driver_stages(self, run_artifacts):
        doc = json.load(open(run_artifacts["trace"]))
        names = {e["name"] for e in doc["traceEvents"]}
        assert {"run", "ingest+gramian", "pca", "emit"} <= names


class TestIngestSpanEmission:
    """The parallel ingest engine's sub-phase observability: a CLI run
    over a JSONL cohort (the CSR-direct route) must land `ingest.slice`
    / `ingest.build` / `ingest.put` spans on the timeline and the
    `ingest_blocks_built_total` / `ingest_block_build_seconds` series
    in the metrics dump — and every artifact must pass the validator's
    ingest schema checks."""

    @pytest.fixture(scope="class")
    def run_artifacts(self, tmp_path_factory):
        from spark_examples_tpu.cli.main import main
        from spark_examples_tpu.genomics.fixtures import synthetic_cohort

        tmp_path = tmp_path_factory.mktemp("obs_ingest")
        root = str(tmp_path / "cohort")
        synthetic_cohort(10, 60, seed=3).dump(root)
        paths = {
            "trace": str(tmp_path / "run.trace.json"),
            "metrics": str(tmp_path / "run.metrics.prom"),
            "manifest": str(tmp_path / "run.manifest.json"),
        }
        old = os.environ.get("SPARK_EXAMPLES_TPU_COMPILE_CACHE")
        os.environ["SPARK_EXAMPLES_TPU_COMPILE_CACHE"] = "0"
        try:
            rc = main(
                [
                    "pca",
                    "--input-path",
                    root,
                    "--block-variants",
                    "32",
                    "--ingest-workers",
                    "2",
                    "--trace-out",
                    paths["trace"],
                    "--metrics-out",
                    paths["metrics"],
                    "--manifest-out",
                    paths["manifest"],
                ]
            )
        finally:
            if old is None:
                os.environ.pop("SPARK_EXAMPLES_TPU_COMPILE_CACHE", None)
            else:
                os.environ["SPARK_EXAMPLES_TPU_COMPILE_CACHE"] = old
        assert rc == 0
        return paths

    def test_ingest_sub_phase_spans_present(self, run_artifacts):
        doc = json.load(open(run_artifacts["trace"]))
        names = {e["name"] for e in doc["traceEvents"]}
        assert {"ingest.slice", "ingest.build", "ingest.put"} <= names

    def test_ingest_metrics_present_with_mode_label(self, run_artifacts):
        prom = open(run_artifacts["metrics"]).read()
        blocks = [
            ln
            for ln in prom.splitlines()
            if ln.startswith("ingest_blocks_built_total")
        ]
        assert blocks and all('mode="' in ln for ln in blocks)
        assert "ingest_block_build_seconds_bucket" in prom
        assert "ingest_block_build_seconds_sum" in prom
        assert "ingest_block_build_seconds_count" in prom

    def test_carriers_gathered_counter_has_mode_label(self, run_artifacts):
        prom = open(run_artifacts["metrics"]).read()
        lines = [
            ln
            for ln in prom.splitlines()
            if ln.startswith("ingest_carriers_gathered_total")
        ]
        assert lines and all('mode="' in ln for ln in lines)
        assert sum(float(ln.rsplit(" ", 1)[1]) for ln in lines) > 0

    @pytest.mark.parametrize(
        "mode",
        [
            pytest.param(
                "native",
                marks=pytest.mark.skipif(
                    not native_available(),
                    reason="native toolchain unavailable",
                ),
            ),
            "python",
        ],
    )
    def test_carriers_gathered_counts_the_path_taken(
        self, mode, tmp_path, monkeypatch
    ):
        """``ingest_carriers_gathered_total{mode}`` counts every carrier
        of a sidecar shard under the path that gathered it: native when
        the library loads, python when ``load()`` returns None."""
        from spark_examples_tpu import native
        from spark_examples_tpu.genomics.fixtures import synthetic_cohort
        from spark_examples_tpu.genomics.shards import Shard
        from spark_examples_tpu.genomics.sources import JsonlSource

        root = str(tmp_path / "cohort")
        synthetic_cohort(10, 60, seed=3).dump(root)
        source = JsonlSource(root)
        index = {c.id: i for i, c in enumerate(source.list_callsets(""))}
        if mode == "python":
            monkeypatch.setattr(native, "load", lambda: None)
        with TelemetrySession() as s:
            indices, _offsets = source.stream_carrying_csr(
                "", Shard("17", 41196311, 41277499), index, None
            )
        counters = s.registry.snapshot()["counters"]
        key = 'ingest_carriers_gathered_total{mode="%s"}'
        other = "python" if mode == "native" else "native"
        assert indices.size > 0
        assert counters[key % mode] == indices.size
        assert counters.get(key % other, 0) == 0

    def test_artifacts_pass_ingest_schema_checks(self, run_artifacts):
        assert validate.validate_trace(run_artifacts["trace"]) == []
        assert validate.validate_metrics(run_artifacts["metrics"]) == []
        assert validate.validate_manifest(run_artifacts["manifest"]) == []

    def test_validator_rejects_unknown_ingest_span(self, tmp_path):
        path = tmp_path / "bad.trace.json"
        path.write_text(
            json.dumps(
                {
                    "traceEvents": [
                        {
                            "ph": "X",
                            "name": "ingest.densify",
                            "pid": 1,
                            "tid": 1,
                            "ts": 0,
                            "dur": 5,
                        }
                    ]
                }
            )
        )
        errs = validate.validate_trace(str(path))
        assert errs and "ingest.densify" in errs[0]

    def test_validator_rejects_modeless_ingest_counter(self, tmp_path):
        path = tmp_path / "bad.metrics.prom"
        path.write_text(
            "# HELP ingest_blocks_built_total blocks\n"
            "# TYPE ingest_blocks_built_total counter\n"
            "ingest_blocks_built_total 5\n"
        )
        errs = validate.validate_metrics(str(path))
        assert errs and "mode" in errs[0]

    def test_sidecar_crc_counter_counts_each_members_bytes(self, tmp_path):
        """A second source over a built sidecar checks every member's
        CRC: ``ingest_sidecar_crc_bytes_total{mode}`` counts the
        members' bytes, all ``serial`` at a test cohort's size."""
        import zipfile

        from spark_examples_tpu.genomics.fixtures import synthetic_cohort
        from spark_examples_tpu.genomics.shards import Shard
        from spark_examples_tpu.genomics.sources import (
            SIDECAR_BASENAME,
            JsonlSource,
        )

        root = str(tmp_path / "cohort")
        synthetic_cohort(10, 60, seed=3).dump(root)
        shard = Shard("17", 41196311, 41277499)
        builder = JsonlSource(root)
        index = {c.id: i for i, c in enumerate(builder.list_callsets(""))}
        builder.stream_carrying_csr("", shard, index, None)
        with zipfile.ZipFile(os.path.join(root, SIDECAR_BASENAME)) as zf:
            members = sum(i.file_size for i in zf.infolist())
        with TelemetrySession() as s:
            JsonlSource(root).stream_carrying_csr("", shard, index, None)
        counters = s.registry.snapshot()["counters"]
        key = 'ingest_sidecar_crc_bytes_total{mode="%s"}'
        assert counters[key % "serial"] == members
        assert counters.get(key % "parallel", 0) == 0

    def test_validator_rejects_modeless_sidecar_crc_counter(self, tmp_path):
        path = tmp_path / "bad.metrics.prom"
        path.write_text(
            "# HELP ingest_sidecar_crc_bytes_total bytes\n"
            "# TYPE ingest_sidecar_crc_bytes_total counter\n"
            "ingest_sidecar_crc_bytes_total 4096\n"
        )
        errs = validate.validate_metrics(str(path))
        assert errs and "mode" in errs[0]

    def test_validator_rejects_modeless_carriers_counter(self, tmp_path):
        path = tmp_path / "bad.metrics.prom"
        path.write_text(
            "# HELP ingest_carriers_gathered_total carriers\n"
            "# TYPE ingest_carriers_gathered_total counter\n"
            "ingest_carriers_gathered_total 40\n"
        )
        errs = validate.validate_metrics(str(path))
        assert errs and "mode" in errs[0]

    def test_manifest_carries_build_histogram(self, run_artifacts):
        mf = json.load(open(run_artifacts["manifest"]))
        hists = {
            k: v
            for k, v in mf["histograms"].items()
            if k.startswith("ingest_block_build_seconds")
        }
        assert hists and any(v["count"] >= 1 for v in hists.values())


class TestTraceContext:
    """PR 16 job-scoped tracing: a trace id is a CONTEXT FIELD stamped
    onto every span/instant emitted under it — not a new span set."""

    def test_default_is_unbound(self):
        assert obs.current_trace_id() is None

    def test_binding_restores_and_none_inherits(self):
        with obs.trace_context("aaaa"):
            assert obs.current_trace_id() == "aaaa"
            # None = "keep whatever is bound": call sites never need a
            # conditional around the context manager.
            with obs.trace_context(None):
                assert obs.current_trace_id() == "aaaa"
            with obs.trace_context("bbbb"):
                assert obs.current_trace_id() == "bbbb"
            assert obs.current_trace_id() == "aaaa"
        assert obs.current_trace_id() is None

    def test_binding_is_thread_local(self):
        seen = []

        def other():
            seen.append(obs.current_trace_id())

        with obs.trace_context("aaaa"):
            t = threading.Thread(target=other)
            t.start()
            t.join()
        assert seen == [None]

    def test_spans_and_instants_carry_the_id_counters_do_not(self):
        with TelemetrySession() as session:
            with obs.trace_context("tid1"):
                with obs.span("fused_finish", n=1):
                    pass
                obs.instant("job_transition", scope="p", to="running")
                obs.counter("serving_queue_depth", depth=3.0)
            with obs.span("fused_finish", n=2):
                pass
            events = session.tracer.to_chrome()["traceEvents"]
        tagged = [
            ev
            for ev in events
            if isinstance(ev.get("args"), dict)
            and ev["args"].get("trace_id") == "tid1"
        ]
        assert {ev["ph"] for ev in tagged} == {"X", "i"}
        # Counter tracks must stay numeric-only (stacked-area
        # rendering) — never stamped.
        counters = [ev for ev in events if ev["ph"] == "C"]
        assert counters and all(
            "trace_id" not in ev["args"] for ev in counters
        )
        # The second span ran outside the context: untagged.
        untagged = [
            ev
            for ev in events
            if ev["ph"] == "X" and ev["args"].get("n") == 2
        ]
        assert untagged and "trace_id" not in untagged[0]["args"]

    def test_events_for_trace_filters_and_orders(self):
        with TelemetrySession() as session:
            with obs.trace_context("tidA"):
                with obs.span("fused_finish", leg=1):
                    pass
            with obs.trace_context("tidB"):
                with obs.span("fused_finish", leg=2):
                    pass
            with obs.trace_context("tidA"):
                obs.instant("job_transition", scope="p", to="done")
            evs = session.tracer.events_for_trace("tidA")
            assert [e["args"].get("leg", None) for e in evs] == [1, None]
            tss = [float(e["ts"]) for e in evs]
            assert tss == sorted(tss)
            assert session.tracer.events_for_trace("nope") == []

    def test_trace_carries_process_provenance(self):
        import socket as socket_mod

        with TelemetrySession() as session:
            with obs.span("fused_finish"):
                pass
            other = session.tracer.to_chrome()["otherData"]
        assert other["host"] == socket_mod.gethostname()
        assert other["pid"] == os.getpid()
        assert isinstance(other["trace_epoch_unix"], float)


class TestFlightRecorder:
    """The crash black box: per-thread overwrite rings, reasoned JSONL
    dumps, hook/handler chaining — always on once installed, cheap
    enough for production (one global read when off)."""

    @pytest.fixture(autouse=True)
    def _clean(self):
        from spark_examples_tpu.obs import flightrec

        flightrec.uninstall()
        yield
        flightrec.uninstall()

    def test_ring_overwrites_keeping_the_last_k(self):
        from spark_examples_tpu.obs import flightrec

        rec = flightrec.FlightRecorder(capacity_per_thread=8)
        for i in range(30):
            rec.note("instant", f"ev{i}", {"i": i})
        snap = rec.snapshot()
        assert len(snap) == 8
        # Exactly the last 8 survive; snapshot order is by timestamp,
        # which can tie at clock resolution — compare as a set.
        assert sorted(r["fields"]["i"] for r in snap) == list(range(22, 30))

    def test_threads_write_locklessly_and_merge_sorted(self):
        from spark_examples_tpu.obs import flightrec

        rec = flightrec.FlightRecorder(capacity_per_thread=64)

        def work(tag):
            for i in range(50):
                rec.note("metric", tag, {"i": i})

        threads = [
            threading.Thread(target=work, args=(f"t{k}",), name=f"w{k}")
            for k in range(4)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        snap = rec.snapshot()
        assert len(snap) == 200
        assert {r["thread"] for r in snap} == {f"w{k}" for k in range(4)}
        tss = [r["ts_unix"] for r in snap]
        assert tss == sorted(tss)

    def test_dump_schema_and_atomicity(self, tmp_path):
        from spark_examples_tpu.obs import flightrec

        rec = flightrec.FlightRecorder()
        rec.note("span_begin", "job.run", {"job_id": "j-1"})
        rec.note("metric", "serving_jobs_total", {"delta": 1.0})
        rec.note("instant", "bad", {"obj": object()})  # unserializable
        path = str(tmp_path / "d" / "flightrec-test.jsonl")
        rec.dump(path, "test")
        lines = [json.loads(l) for l in open(path)]
        header, records = lines[0], lines[1:]
        assert header["schema"] == "spark_examples_tpu.flightrec/v1"
        assert header["reason"] == "test"
        assert header["pid"] == os.getpid()
        assert [r["name"] for r in records] == [
            "job.run",
            "serving_jobs_total",
            "bad",
        ]
        assert records[2]["unserializable_fields"] is True
        assert not os.path.exists(path + ".tmp")  # tmp+rename, no ruins

    def test_ambient_helpers_tap_the_recorder_without_a_session(
        self, tmp_path
    ):
        """The black box works with tracing OFF — that is its reason to
        exist: span/instant transitions and metric deltas land in the
        rings even when no telemetry session is active."""
        from spark_examples_tpu.obs import flightrec

        assert not obs.collection_active()
        flightrec.install(str(tmp_path), handle_signals=False)
        with obs.span("job.run", job_id="j-9"):
            obs.instant("job_transition", scope="p", to="running")
        reg = obs.get_registry()
        reg.counter("serving_jobs_total").labels(outcome="done").inc()
        snap = flightrec.get_recorder().snapshot()
        kinds = {(r["kind"], r["name"]) for r in snap}
        assert ("span_begin", "job.run") in kinds
        assert ("span_end", "job.run") in kinds
        assert ("instant", "job_transition") in kinds
        assert ("metric", "serving_jobs_total") in kinds
        path = flightrec.dump_now("watchdog")
        assert path and path.endswith("flightrec-watchdog.jsonl")
        assert os.path.exists(path)

    def test_install_is_idempotent_and_uninstall_restores(self, tmp_path):
        import sys

        from spark_examples_tpu.obs import flightrec

        prev_hook = sys.excepthook
        rec1 = flightrec.install(str(tmp_path), handle_signals=False)
        rec2 = flightrec.install(str(tmp_path / "other"), handle_signals=False)
        assert rec1 is rec2
        assert sys.excepthook is not prev_hook
        flightrec.uninstall()
        assert sys.excepthook is prev_hook
        assert flightrec.get_recorder() is None
        flightrec.note("instant", "after", None)  # no-op, no crash

    def test_excepthook_dumps_then_chains(self, tmp_path, capsys):
        import sys

        from spark_examples_tpu.obs import flightrec

        seen = []
        prev_hook = sys.excepthook
        sys.excepthook = lambda *a: seen.append(a)
        try:
            flightrec.install(str(tmp_path), handle_signals=False)
            flightrec.note("instant", "before_crash", None)
            try:
                raise RuntimeError("boom")
            except RuntimeError:
                sys.excepthook(*sys.exc_info())
            dump = os.path.join(str(tmp_path), "flightrec-exception.jsonl")
            assert os.path.exists(dump)
            lines = [json.loads(l) for l in open(dump)]
            assert lines[0]["reason"] == "exception"
            names = [r["name"] for r in lines[1:]]
            assert "before_crash" in names
            assert "unhandled_exception" in names
            assert len(seen) == 1  # the previous hook still ran
        finally:
            flightrec.uninstall()
            sys.excepthook = prev_hook

    def test_periodic_flusher_writes_last_snapshot(self, tmp_path):
        import time as time_mod

        from spark_examples_tpu.obs import flightrec

        flightrec.install(
            str(tmp_path), flush_interval_s=0.05, handle_signals=False
        )
        flightrec.note("instant", "tick", None)
        last = os.path.join(str(tmp_path), "flightrec-last.jsonl")
        deadline = time_mod.time() + 5
        while time_mod.time() < deadline and not os.path.exists(last):
            time_mod.sleep(0.02)
        assert os.path.exists(last), "periodic flusher never wrote"
        lines = [json.loads(l) for l in open(last)]
        assert lines[0]["reason"] == "periodic"


class TestScrapeWhileWriting:
    """PR 16 satellite: a /metrics scrape (to_prometheus) racing hot
    writers must neither tear a histogram triplet, block the writers,
    nor double-count — pinned with the lock-check backstop armed."""

    @pytest.fixture(autouse=True)
    def _lock_check(self, monkeypatch):
        monkeypatch.setenv("SPARK_EXAMPLES_TPU_LOCK_CHECK", "1")
        yield

    def test_concurrent_scrape_is_consistent(self):
        reg = MetricsRegistry()
        n_threads, per_thread = 4, 3000
        stop = threading.Event()
        scrapes = []
        errors = []

        def writer(k):
            c = reg.counter("scrape_race_total", "writes")
            g = reg.gauge("scrape_race_inflight", "now")
            h = reg.histogram(
                "scrape_race_seconds", "lat", buckets=(0.1, 1.0)
            )
            try:
                for i in range(per_thread):
                    c.labels(worker=str(k)).inc()
                    g.set(float(i))
                    h.observe(0.05 if i % 2 else 5.0)
            except Exception as e:  # noqa: BLE001 — surfaced below
                errors.append(e)

        def scraper():
            try:
                while not stop.is_set():
                    scrapes.append(reg.to_prometheus())
            except Exception as e:  # noqa: BLE001 — surfaced below
                errors.append(e)

        threads = [
            threading.Thread(target=writer, args=(k,))
            for k in range(n_threads)
        ]
        s = threading.Thread(target=scraper)
        s.start()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        stop.set()
        s.join(timeout=10)
        assert not s.is_alive() and not errors, errors
        assert scrapes, "scraper never completed a pass"
        # No double-count / no lost writes: the final exposition sums
        # to exactly what the writers wrote.
        final = reg.to_prometheus()
        import re as re_mod

        totals = [
            float(m.group(1))
            for m in re_mod.finditer(
                r'scrape_race_total\{worker="\d+"\} ([0-9.e+]+)', final
            )
        ]
        assert sum(totals) == n_threads * per_thread
        assert f"scrape_race_seconds_count {n_threads * per_thread}" in final
        # No torn triplet in ANY mid-run scrape: bucket lines never
        # appear without their sum/count (the schema checker's rule,
        # applied to every racing exposition).
        for text in scrapes[-5:]:
            if "scrape_race_seconds_bucket" in text:
                assert "scrape_race_seconds_sum" in text
                assert "scrape_race_seconds_count" in text
