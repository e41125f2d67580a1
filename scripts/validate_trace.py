#!/usr/bin/env python
"""Schema-check telemetry artifacts: trace JSON, metrics dump, manifest.

Malformed telemetry must fail FAST — a trace Perfetto silently refuses
to load, or a manifest a later tooling round can't parse, is worse than
none because nobody notices until the artifact is needed. This script is
both a CLI (CI/operators) and an importable library (the tier-1 tests
call the ``validate_*`` functions directly on every pipeline-emitted
artifact).

Usage::

    python scripts/validate_trace.py --trace run.trace.json \
        --metrics run.metrics.prom --manifest run.manifest.json

Each flag is optional; exit status is non-zero if ANY given file fails,
with one line per problem on stderr.

No dependencies beyond the standard library — runs anywhere, including
images without jax.
"""

from __future__ import annotations

import argparse
import json
import re
import sys
from typing import Any, Dict, List

__all__ = [
    "sparse_overlap_proven",
    "validate_trace",
    "validate_metrics",
    "validate_manifest",
    "main",
]

# Chrome trace-event phases this system emits.
_KNOWN_PHASES = {"X", "B", "E", "i", "I", "C", "M"}

# Ingest sub-phase span contract (the parallel native ingest engine):
# every `ingest.<sub>` span must be one of these — a typo'd sub-phase
# name would silently vanish from the stage attribution the next
# capture window relies on. (`ingest+gramian`, the driver STAGE name,
# is not an `ingest.` span and is unaffected.)
_INGEST_SPANS = {
    "ingest.fetch",  # one shard's fetch+decode (wire frame / sidecar)
    "ingest.stream", # the whole fused-CSR shard stream (fetch workers)
    "ingest.slice",  # CSR pairs -> per-block windows
    "ingest.build",  # window -> packed block (native scatter / numpy)
    "ingest.pack",   # legacy densified-block host pack
    "ingest.put",    # device staging inside the prefetch feed, until
                     # the transfer is ready
    "ingest.sidecar_open",  # one source's CSR sidecar load + check
}

# Device-feed span contract (arrays/feed.py, ops/sparse.py): every
# `feed.<sub>` span must be one of these — the benchmark splits the
# device's idle time into "starved by the feed" and "blocked in
# dispatch" from exactly this set. Not `ingest.`: the union of ingest
# spans must not count the dispatcher's waits.
_FEED_SPANS = {
    "feed.wait",  # the dispatching thread's wait for its next input
}

# Analysis job tier span contract (serving/): every `job.<sub>` span
# must be one of these — same closed-set discipline as the ingest
# sub-phases, so a renamed job span can never silently vanish from the
# timeline the service's state-transition story depends on.
_JOB_SPANS = {
    "job.run",     # one job's execution (ingest -> gramian -> pca)
    "job.replay",  # crash-recovery journal replay at tier startup
    "job.delta",   # one cached-ancestor rank-k Gramian correction
                   # (added/removed sample counts in args)
    "job.gang",    # one gang-batched Gramian dispatch (size + member
                   # job ids in args)
    "job.adopt",   # one expired-peer journal adoption (peer replica id
                   # + fencing token in args)
}

# Sparse-aware Gramian span contract (ops/sparse.py + the mesh-tiled
# accumulator in parallel/sharded.py): every `gramian.sparse.<sub>`
# span must be one of these — the biobank-trajectory capture windows
# attribute scatter-vs-dense routing from exactly this set.
_SPARSE_SPANS = {
    "gramian.sparse.accumulate",  # one whole window-stream accumulation
    "gramian.sparse.window",      # one CSR window (route=scatter|dense)
    "gramian.sparse.allgather",   # one pod-sparse exchange phase
                                  # (header/confirm/carrier across
                                  # processes)
    "gramian.sparse.slot",        # one pipelined pod protocol step on
                                  # the sync thread (the whole slot:
                                  # gang pull + exchanges + payload)
}

# Mesh finish span contract (parallel/sharded.sharded_pcoa): every
# `finish.<sub>` span must be one of these — the benchmark's mesh cell
# reads the finish's centring and eigensolve from exactly this set.
_FINISH_SPANS = {
    "finish.mesh.center",  # double-centring the sharded Gramian
    "finish.mesh.eig",     # the subspace eigensolve (iterations and the
                           # final residual in args)
}

# Gramian-free sketch engine span contract (ops/sketch.py + the mesh
# half in parallel/sharded.py): every `gramian.sketch.<sub>` span must
# be one of these — the million-sample-trajectory captures attribute
# streamed-panel accumulation vs the TSQR/Nyström finish from exactly
# this set.
_SKETCH_SPANS = {
    "gramian.sketch.accumulate",  # one whole panel pass over the
                                  # window stream (sketch_pass in args)
    "gramian.sketch.window",      # one CSR window applied to the panel
                                  # (route=scatter|dense)
    "gramian.sketch.finish",      # the TSQR/Nyström eigensolve
}

# Read-level kernel pipeline span contract (models/pairhmm.py): every
# `pairhmm.<sub>` span must be one of these — the reads-workload
# capture windows attribute host-prep vs device-forward time from
# exactly this set.
_PAIRHMM_SPANS = {
    "pairhmm.bucket",   # one shard's host prep: read streaming,
                        # consensus vote, pair building + bucketing
    "pairhmm.forward",  # one batched forward dispatch (bucket + pair
                        # count in args)
}

# Pod-exchange instant contract (parallel/podstream.py): every `pod.`
# event must be one of these — merge_pod_trace.py's clock-offset
# estimator keys on exactly this name and its
# me/peer/step/stream/send_unix/recv_unix args, so a rename would
# silently break pod trace merging.
_POD_INSTANTS = {
    "pod.exchange_ts",  # one peer's header round-trip timestamps
                        # (send_unix/recv_unix) for one protocol step
}

# Prometheus exposition line shapes (text format 0.0.4).
_PROM_COMMENT = re.compile(r"^# (HELP|TYPE) [a-zA-Z_:][a-zA-Z0-9_:]* ?.*$")
_PROM_SAMPLE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^{}]*\})? "
    r"[-+]?([0-9.eE+-]+|[Nn]a[Nn]|[+-]?[Ii]nf)$"
)

MANIFEST_SCHEMA = "spark_examples_tpu.run_manifest/v1"
_MANIFEST_REQUIRED = (
    "schema",
    "created_unix",
    "config",
    "environment",
    "stages",
    "counters",
    "histograms",
)


def sparse_overlap_proven(events: List[Dict[str, Any]]) -> bool:
    """True when some step w+1 pod-sparse exchange span begins before
    step w's accumulation (window) span ends on a Chrome-trace event
    list — the pipelined carrier stream's overlap PROOF. The pod-sim
    CI leg, the bench pod leg, and the pod test worker all assert
    through THIS predicate, so the span-schema coupling (names and the
    ``step``/``stream`` args) lives in one place next to the closed
    span sets it depends on. Comparisons are scoped per ``stream``:
    step numbers restart for every accumulation stream, and comparing
    across streams could prove "overlap" between a later stream's
    windows and an earlier stream's exchanges.
    """
    window_end: Dict[Any, float] = {}
    for ev in events:
        if (
            ev.get("ph") == "X"
            and ev.get("name") == "gramian.sparse.window"
        ):
            args = ev.get("args", {})
            step = args.get("step")
            if step is not None:
                key = (args.get("stream"), int(step))
                window_end[key] = ev["ts"] + ev["dur"]
    for ev in events:
        if (
            ev.get("ph") == "X"
            and ev.get("name") == "gramian.sparse.allgather"
        ):
            args = ev.get("args", {})
            prev = (args.get("stream"), int(args.get("step", 0)) - 1)
            if prev in window_end and ev["ts"] < window_end[prev]:
                return True
    return False


def _load_json(path: str, errors: List[str]) -> Any:
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError) as e:
        errors.append(f"{path}: not readable JSON: {e}")
        return None


def validate_trace(path: str) -> List[str]:
    """Errors for a Chrome-trace-event JSON file ([] = valid)."""
    errors: List[str] = []
    doc = _load_json(path, errors)
    if doc is None:
        return errors
    if not isinstance(doc, dict) or "traceEvents" not in doc:
        return [f"{path}: expected object with 'traceEvents'"]
    events = doc["traceEvents"]
    if not isinstance(events, list) or not events:
        return [f"{path}: traceEvents must be a non-empty list"]
    for i, ev in enumerate(events):
        where = f"{path}: traceEvents[{i}]"
        if not isinstance(ev, dict):
            errors.append(f"{where}: not an object")
            continue
        ph = ev.get("ph")
        if ph not in _KNOWN_PHASES:
            errors.append(f"{where}: unknown phase {ph!r}")
            continue
        if not isinstance(ev.get("name"), str) or not ev["name"]:
            errors.append(f"{where}: missing/empty name")
        elif (
            ev["name"].startswith("ingest.")
            and ev["name"] not in _INGEST_SPANS
        ):
            errors.append(
                f"{where}: unknown ingest sub-phase span "
                f"{ev['name']!r} (expected one of "
                f"{sorted(_INGEST_SPANS)})"
            )
        elif (
            ev["name"].startswith("feed.")
            and ev["name"] not in _FEED_SPANS
        ):
            errors.append(
                f"{where}: unknown device-feed span {ev['name']!r} "
                f"(expected one of {sorted(_FEED_SPANS)})"
            )
        elif (
            ev["name"].startswith("job.")
            and ev["name"] not in _JOB_SPANS
        ):
            errors.append(
                f"{where}: unknown job-tier span {ev['name']!r} "
                f"(expected one of {sorted(_JOB_SPANS)})"
            )
        elif (
            ev["name"].startswith("gramian.sparse.")
            and ev["name"] not in _SPARSE_SPANS
        ):
            errors.append(
                f"{where}: unknown sparse-gramian span "
                f"{ev['name']!r} (expected one of "
                f"{sorted(_SPARSE_SPANS)})"
            )
        elif (
            ev["name"].startswith("finish.")
            and ev["name"] not in _FINISH_SPANS
        ):
            errors.append(
                f"{where}: unknown finish span {ev['name']!r} "
                f"(expected one of {sorted(_FINISH_SPANS)})"
            )
        elif (
            ev["name"].startswith("gramian.sketch.")
            and ev["name"] not in _SKETCH_SPANS
        ):
            errors.append(
                f"{where}: unknown sketch-engine span "
                f"{ev['name']!r} (expected one of "
                f"{sorted(_SKETCH_SPANS)})"
            )
        elif (
            ev["name"].startswith("pairhmm.")
            and ev["name"] not in _PAIRHMM_SPANS
        ):
            errors.append(
                f"{where}: unknown pairhmm span {ev['name']!r} "
                f"(expected one of {sorted(_PAIRHMM_SPANS)})"
            )
        elif (
            ev["name"].startswith("pod.")
            and ev["name"] not in _POD_INSTANTS
        ):
            errors.append(
                f"{where}: unknown pod-exchange event {ev['name']!r} "
                f"(expected one of {sorted(_POD_INSTANTS)})"
            )
        if not isinstance(ev.get("pid"), int):
            errors.append(f"{where}: pid must be an int")
        if ph != "M":
            if not isinstance(ev.get("ts"), (int, float)):
                errors.append(f"{where}: ts must be a number")
            elif ev["ts"] < 0:
                errors.append(f"{where}: negative ts")
        if ph == "X":
            dur = ev.get("dur")
            if not isinstance(dur, (int, float)) or dur < 0:
                errors.append(f"{where}: complete event needs dur >= 0")
        if "args" in ev and not isinstance(ev["args"], dict):
            errors.append(f"{where}: args must be an object")
    return errors


# Binary wire tier (genomics/wire.py) metric contract: counters carry a
# transport label ("http"/"grpc"); the decode histogram exposes the full
# Prometheus triplet. Checked only when present — artifacts from runs
# that never touched the frame tier stay valid.
_WIRE_COUNTERS = ("wire_frames_total", "wire_frame_bytes_total")
_WIRE_HISTOGRAM = "wire_frame_decode_seconds"

# Parallel-ingest metric contract: the block and carrier counters carry
# a mode label ("native"/"python" — which path built the block or
# gathered the carriers), the sidecar CRC byte counter one too
# ("parallel"/"serial" — how each member's CRC was checked), and the
# build-latency histogram exposes the full Prometheus triplet. Checked
# only when present, like the wire metrics.
_INGEST_COUNTERS = (
    "ingest_blocks_built_total",
    "ingest_carriers_gathered_total",
    "ingest_sidecar_crc_bytes_total",
)
_INGEST_HISTOGRAM = "ingest_block_build_seconds"

# Serving/resilience metric contract: each of these counters must carry
# the named label on every sample (and GL003 statically requires the
# registration sites to chain it). Checked only when present, like the
# wire/ingest metrics.
_LABELED_COUNTERS = {
    "breaker_probe_total": "outcome",     # half-open probe outcomes
    "cold_stream_shards_total": "stage",  # fetched/accumulated per shard
    "collective_check_steps_total": "outcome",  # agree/divergence per
                                          # cross-checked pod step
    "pairhmm_pairs_total": "bucket",      # scored pairs per (read, hap)
                                          # length bucket (rRxhH)
    "serving_delta_jobs_total": "outcome",  # hit/fallback/miss
    "serving_jobs_total": "outcome",      # done/failed/cached/deduped
    "serving_lease_total": "outcome",     # acquired/renewed/lost/takeover/
                                          # degraded/recovered/released/
                                          # rejected_write
    "serving_shed_total": "reason",       # queue_full/quota
    "sketch_windows_total": "route",      # scatter/dense per sketch-
                                          # panel window
    "sparse_gramian_windows_total": "route",  # scatter/dense per window
    "sparse_pod_coalesced_windows_total": "mode",  # gang/solo per step
    "sparse_scatter_path_windows_total": "path",  # scan/mxu/mxu-tile
                                          # per window
    "sparse_pod_sync_total": "outcome",   # synced/drained/producer-error/
                                          # route-divergence/dtype-divergence
}

# Serving-tier plain histograms: no label contract, but when present
# the full Prometheus triplet must be exposed, and GL003 requires a
# live registration site for each name (a renamed emission can never
# leave a dead schema entry).
_SERVING_HISTOGRAMS = (
    "serving_gang_size",
    "serving_queue_age_seconds",
)

# Serving-tier gauges: current-value series the /metrics and /statusz
# surfaces expose; GL003 requires a live registration site for each
# (same no-dead-schema-entry discipline as the histograms).
_SERVING_GAUGES = (
    "serving_inflight_jobs",
    "serving_queue_depth",
    "serving_store_degraded",
)


def _check_wire_metrics(path: str, sample_lines: List[str]) -> List[str]:
    errors: List[str] = []
    names = set()
    for line in sample_lines:
        name = re.match(r"[a-zA-Z_:][a-zA-Z0-9_:]*", line).group(0)
        names.add(name)
        if name in _WIRE_COUNTERS and 'transport="' not in line:
            errors.append(
                f"{path}: {name} sample missing its transport label: "
                f"{line!r}"
            )
        if (
            name in _INGEST_COUNTERS
            or name.startswith(_INGEST_HISTOGRAM)
        ) and 'mode="' not in line:
            errors.append(
                f"{path}: {name} sample missing its mode label: {line!r}"
            )
        required = _LABELED_COUNTERS.get(name)
        if required is not None and f'{required}="' not in line:
            errors.append(
                f"{path}: {name} sample missing its {required} label: "
                f"{line!r}"
            )
    for hist in (
        _WIRE_HISTOGRAM,
        _INGEST_HISTOGRAM,
        *_SERVING_HISTOGRAMS,
    ):
        if f"{hist}_bucket" in names:
            for suffix in ("_sum", "_count"):
                if f"{hist}{suffix}" not in names:
                    errors.append(
                        f"{path}: {hist} histogram exposes "
                        f"buckets but no {suffix} series"
                    )
    return errors


def validate_metrics(path: str) -> List[str]:
    """Errors for a Prometheus text exposition file ([] = valid)."""
    errors: List[str] = []
    try:
        with open(path) as f:
            text = f.read()
    except OSError as e:
        return [f"{path}: unreadable: {e}"]
    lines = [ln for ln in text.splitlines() if ln.strip()]
    if not lines:
        return [f"{path}: empty exposition"]
    samples = 0
    sample_lines: List[str] = []
    for lineno, line in enumerate(lines, 1):
        if line.startswith("#"):
            if not _PROM_COMMENT.match(line):
                errors.append(
                    f"{path}:{lineno}: malformed comment line: {line!r}"
                )
            continue
        if not _PROM_SAMPLE.match(line):
            errors.append(
                f"{path}:{lineno}: malformed sample line: {line!r}"
            )
            continue
        samples += 1
        sample_lines.append(line)
    if samples == 0:
        errors.append(f"{path}: no metric samples")
    errors.extend(_check_wire_metrics(path, sample_lines))
    return errors


def validate_manifest(path: str) -> List[str]:
    """Errors for a run-manifest JSON file ([] = valid)."""
    errors: List[str] = []
    doc = _load_json(path, errors)
    if doc is None:
        return errors
    if not isinstance(doc, dict):
        return [f"{path}: manifest must be a JSON object"]
    for key in _MANIFEST_REQUIRED:
        if key not in doc:
            errors.append(f"{path}: missing required key {key!r}")
    if errors:
        return errors
    if doc["schema"] != MANIFEST_SCHEMA:
        errors.append(
            f"{path}: schema {doc['schema']!r} != {MANIFEST_SCHEMA!r}"
        )
    if not isinstance(doc["created_unix"], (int, float)):
        errors.append(f"{path}: created_unix must be a number")
    stages = doc["stages"]
    if not isinstance(stages, dict):
        errors.append(f"{path}: stages must be an object")
    else:
        for name, st in stages.items():
            if (
                not isinstance(st, dict)
                or not isinstance(st.get("seconds"), (int, float))
                or st["seconds"] < 0
                or not isinstance(st.get("count"), int)
            ):
                errors.append(
                    f"{path}: stages[{name!r}] needs seconds >= 0 and "
                    "an int count"
                )
    for section in ("counters", "gauges"):
        block = doc.get(section, {})
        if not isinstance(block, dict):
            errors.append(f"{path}: {section} must be an object")
            continue
        for key, value in block.items():
            if not isinstance(value, (int, float)):
                errors.append(
                    f"{path}: {section}[{key!r}] must be numeric"
                )
    hists = doc["histograms"]
    if not isinstance(hists, dict):
        errors.append(f"{path}: histograms must be an object")
    else:
        for key, summary in hists.items():
            if not isinstance(summary, dict):
                errors.append(
                    f"{path}: histograms[{key!r}] must be an object"
                )
                continue
            for field in ("count", "sum", "mean", "p50", "p90", "p99"):
                if not isinstance(summary.get(field), (int, float)):
                    errors.append(
                        f"{path}: histograms[{key!r}] missing numeric "
                        f"{field!r}"
                    )
    return errors


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        description="Schema-check telemetry artifacts"
    )
    p.add_argument("--trace", default=None, help="Chrome trace JSON")
    p.add_argument(
        "--metrics", default=None, help="Prometheus text exposition"
    )
    p.add_argument("--manifest", default=None, help="Run manifest JSON")
    args = p.parse_args(argv)
    if not (args.trace or args.metrics or args.manifest):
        p.error("nothing to validate: pass --trace/--metrics/--manifest")
    errors: List[str] = []
    checked: Dict[str, int] = {}
    if args.trace:
        errs = validate_trace(args.trace)
        checked[args.trace] = len(errs)
        errors.extend(errs)
    if args.metrics:
        errs = validate_metrics(args.metrics)
        checked[args.metrics] = len(errs)
        errors.extend(errs)
    if args.manifest:
        errs = validate_manifest(args.manifest)
        checked[args.manifest] = len(errs)
        errors.extend(errs)
    for err in errors:
        print(err, file=sys.stderr)
    for path, n in checked.items():
        print(f"{path}: {'OK' if n == 0 else f'{n} problem(s)'}")
    return 1 if errors else 0


if __name__ == "__main__":
    sys.exit(main())
