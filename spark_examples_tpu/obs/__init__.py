"""Unified telemetry: span tracing, metrics registry, run manifests.

The reference repo's only observability was six driver-merged
accumulators plus the Spark UI (SURVEY.md §5); rounds 1-5 reproduced
exactly that (``utils/stats.py``) plus a wall-clock ``StageTimer``
(``utils/tracing.py``) — not enough to diagnose the round-5 remote-tier
stalls without log archaeology. This package is the
first-class telemetry layer every subsequent perf PR measures itself
with. Three pillars:

1. **Span tracer** (:mod:`.tracer`): thread-safe spans/instants emitted
   as Chrome trace-event JSON — loadable in Perfetto (ui.perfetto.dev)
   and TensorBoard — and optionally mirrored into ``jax.profiler``
   annotations so host-side spans line up with device traces on one
   timeline. ``utils.tracing.StageTimer`` is now a thin shim over it.
2. **Metrics registry** (:mod:`.metrics`): counters, gauges, and latency
   histograms with a Prometheus text exposition and a JSONL sink. The
   six ``IoStats`` parity accumulators surface here via a zero-hot-path
   collector; RPC transports feed ``genomics_rpc_latency_seconds``.
3. **Run manifest** (:mod:`.manifest`): one machine-readable JSON
   artifact per pipeline/bench run — config, JAX/device topology, stage
   timings, counters, histogram summaries — so ``BENCH_*.json`` rounds
   carry per-stage breakdowns instead of a single wall-clock number.

Ambient use: the CLI (``--trace-out/--metrics-out/--manifest-out``) and
``bench.py`` open a :func:`telemetry_session`; library code records
through the module-level ``span``/``instant``/``observe_rpc`` helpers,
which are near-zero-cost no-ops when no session is active — the data
plane pays nothing unless someone asked for telemetry.
"""

from spark_examples_tpu.obs import flightrec
from spark_examples_tpu.obs.tracer import (
    SpanTracer,
    collection_active,
    counter,
    current_trace_id,
    get_tracer,
    instant,
    set_tracer,
    span,
    trace_context,
)
from spark_examples_tpu.obs.metrics import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    count_retry,
    get_registry,
    observe_rpc,
    register_collector,
    rpc_timer,
    set_registry,
)
from spark_examples_tpu.obs.manifest import build_manifest, write_manifest
from spark_examples_tpu.obs.session import (
    TelemetrySession,
    flush_telemetry,
    telemetry_session,
)

__all__ = [
    "SpanTracer",
    "collection_active",
    "counter",
    "current_trace_id",
    "flightrec",
    "get_tracer",
    "set_tracer",
    "span",
    "instant",
    "trace_context",
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "count_retry",
    "get_registry",
    "set_registry",
    "register_collector",
    "rpc_timer",
    "observe_rpc",
    "build_manifest",
    "write_manifest",
    "TelemetrySession",
    "telemetry_session",
    "flush_telemetry",
]
