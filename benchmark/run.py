#!/usr/bin/env python3
"""Time to coordinates on the chip: one cell of BENCHMARK.json, one run.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A cell is a configuration (``benchmark/configs/<config>.json``: the cohort
and its generator, the limits of the comparison) under a traffic mix
(``benchmark/traffic/<mix>.json``: the ``cli pca`` flags it adds). Set-up
generates the cohort from the seed, writes it as a light cohort mirror
and runs one analysis to load or compile every program. The window then
runs analyses back to back, each through a fresh ``VariantsPcaDriver``
built from the CLI's own parser (``ingest_gramian`` then ``compute_pca``),
until ``--seconds`` have passed, and finishes the analysis in flight.

After the window every analysis's Gramian and coordinates are compared
with the plain reference (``benchmark/reference.py``), as far as the host's
budget for them reaches (``keep_count``); past it, the first analyses'
and the last's. The last line of
stdout is the result; the last lines of stderr are the numbers compared,
each with its limit. ``--trace 1`` records the window with the profiler
and the program's spans and reports the cell's per-layer metrics
(``benchmark/metrics/<metric>.py``) in place of the end-to-end ones.

Without a TPU, or with fewer chips than the cell asks for, it exits 2
and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402
from dataclasses import dataclass, field  # noqa: E402
from typing import List, Optional  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import reference  # noqa: E402

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def _say(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# -- finding cells, configurations, mixes and metrics by name ----------------


def load_spec(root: str) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def find(entries: List[dict], name: str, what: str) -> dict:
    for entry in entries:
        if entry["name"] == name:
            return entry
    raise SystemExit(f"run.py: no {what} named {name!r} in BENCHMARK.json")


def load_json(root: str, *parts: str) -> dict:
    with open(os.path.join(root, *parts)) as f:
        return json.load(f)


def load_module(root: str, kind: str, name: str):
    """``benchmark/<kind>/<name>.py`` as a module."""
    path = os.path.join(root, "benchmark", kind, f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{kind}_{name}", path)
    if spec is None or not os.path.exists(path):
        raise SystemExit(f"run.py: no {kind} file {path}")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]


def resolve_cell(root: str, workload: str) -> Cell:
    spec = load_spec(root)
    wl = find(spec["workloads"], workload, "workload")
    cfg_entry = find(spec["configs"], wl["config"], "config")

    def applies(metric):
        return workload in metric.get("workloads", [workload])

    return Cell(
        name=workload,
        chips=int(wl["chips"]),
        config=load_json(root, cfg_entry["file"]),
        traffic=load_json(root, "benchmark", "traffic", wl["traffic"] + ".json"),
        end_to_end=[m for m in spec["end_to_end"] if applies(m)],
        per_layer=[m for m in spec["per_layer"] if applies(m)],
    )


# -- the device --------------------------------------------------------------


def require_devices(chips: int) -> Optional[list]:
    """The devices the cell runs on, or None (after saying why) when JAX
    finds no TPU or fewer chips than the cell asks for."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        _say(f"run.py: no TPU: JAX found platform {devices[0].platform!r}")
        return None
    if len(devices) < chips:
        _say(f"run.py: the cell needs {chips} chips, JAX found "
             f"{len(devices)}")
        return None
    return devices


def use_compile_cache(root: str) -> str:
    """JAX's persistent compile cache at ``<checkout>/.jax_cache``, the
    directory the program's own cache helper is then handed."""
    import jax

    path = os.path.join(root, ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    from spark_examples_tpu.utils.compile_cache import enable_persistent_cache

    return enable_persistent_cache()


class CompileCounter:
    """Backend compiles, from JAX's monitoring event."""

    def __init__(self):
        import jax

        self.count = 0
        self.seconds = 0.0
        self._lock = threading.Lock()

        def listen(event, duration, **_):
            if event == COMPILE_EVENT:
                with self._lock:
                    self.count += 1
                    self.seconds += duration

        jax.monitoring.register_event_duration_secs_listener(listen)


# -- one analysis, as ``cli pca`` runs it ------------------------------------


def analysis(cohort_dir: str, references: str, flags: List[str]):
    """→ (device G, coordinate tuples, callset id → G row)."""
    from spark_examples_tpu.cli.main import build_parser
    from spark_examples_tpu.genomics.fixtures import DEFAULT_VARIANT_SET_ID
    from spark_examples_tpu.genomics.sources import JsonlSource
    from spark_examples_tpu.models.pca import VariantsPcaDriver
    from spark_examples_tpu.utils.config import pca_config_from_args

    args = build_parser().parse_args(
        ["pca", "--input-path", cohort_dir, "--references", references,
         *flags])
    conf = pca_config_from_args(args)
    if not args.variant_set_ids:
        conf.variant_set_ids = [DEFAULT_VARIANT_SET_ID]
    driver = VariantsPcaDriver(conf, JsonlSource(cohort_dir))
    g = driver.ingest_gramian()
    coords = driver.compute_pca(g)
    return g, coords, dict(driver.index.indexes)


def route_counts() -> dict:
    from spark_examples_tpu import obs

    counters = obs.get_registry().snapshot()["counters"]
    return {k: v for k, v in counters.items()
            if k.startswith(("sparse_gramian_windows_total",
                             "sparse_scatter_path_windows_total"))}


def host_io() -> dict:
    """This process's bytes read from storage and major page faults, where
    the OS reports them (Linux ``/proc``)."""
    out = {}
    try:
        with open("/proc/self/io") as f:
            for line in f:
                key, _, value = line.partition(":")
                if key == "read_bytes":
                    out[key] = int(value)
        with open("/proc/self/stat") as f:
            out["major_faults"] = int(f.read().rsplit(")", 1)[1].split()[9])
    except OSError:
        pass
    return out


# -- what the per-layer readers see ------------------------------------------


@dataclass
class Observed:
    analyses: int
    compiles: int
    work: dict
    peaks: dict
    spans: List[tuple] = field(default_factory=list)  # (name, t0, t1, tid)
    trace: object = None  # benchmark.trace.Trace
    chips: int = 1  # the cell's; device times are averaged over them


def _session_spans(session) -> List[tuple]:
    """The program's spans as (name, start s, end s, thread)."""
    return [
        (ev["name"], ev["ts"] * 1e-6, (ev["ts"] + ev["dur"]) * 1e-6,
         ev["tid"])
        for ev in session.tracer.to_chrome()["traceEvents"]
        if ev.get("ph") == "X"
    ]


def _work(cohort, cell) -> dict:
    return {"n": cohort.n_samples, "v": cohort.n_sites, "nnz": cohort.nnz,
            "sum_k2": cohort.sum_k2(), "k": int(cell.config["num_pc"])}


def peak_memory(devices) -> int:
    """The largest ``peak_bytes_in_use`` over the devices (0 where the
    backend reports none)."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


# The host's budget for the Gramians the window keeps for the comparison,
# 4 N^2 bytes each: every analysis of today's cells (at most about 340
# Gramians of 41 MB), the first and the last at N = 49,960 (10 GB each).
HOST_KEEP_BYTES = 16 << 30


def keep_count(n_samples: int) -> int:
    """How many analyses' answers the window keeps: the first ones, while
    their float32 Gramians fit in ``HOST_KEEP_BYTES``, and the last."""
    return max(2, HOST_KEEP_BYTES // (4 * n_samples * n_samples))


# -- correctness -------------------------------------------------------------


def reference_answers(cohort, num_pc: int, devices=None) -> tuple:
    """The plain reference's (G, coordinates), G tiled over ``devices``."""
    t0 = time.perf_counter()
    g_ref = reference.gramian(cohort, devices)
    t1 = time.perf_counter()
    c_ref = reference.coordinates(g_ref, num_pc)
    _say(f"reference: G in {t1 - t0:.2f} s, coordinates in "
         f"{time.perf_counter() - t1:.2f} s")
    return g_ref, c_ref


def compare(cohort, results, num_pc: int, limits: dict,
            answers=None, devices=None) -> dict:
    """Each analysis's G and coordinates against the plain reference's
    ``answers`` (G, coordinates), computed here on ``devices`` when not
    given. G_ref is put in the analyses' row order once (they share it)
    and compared a row block at a time, never as an N x N float64."""
    import numpy as np

    if answers is None:
        answers = reference_answers(cohort, num_pc, devices)
    g_ref, c_ref = answers
    order = {cid: i for i, cid in enumerate(cohort.sample_ids)}
    wants = {}  # G_ref in each row order the analyses use
    g_err = coord_err = 0.0
    for g, coords, rows in results:
        perm = np.array([order[cid] for cid, _ in sorted(
            rows.items(), key=lambda kv: kv[1])])
        key = perm.tobytes()
        if key not in wants:
            wants[key] = reference.permuted(g_ref, perm)
        g_err = max(g_err, reference.g_err(g, wants[key]))
        got = np.zeros((len(order), 2))
        for cid, pc1, pc2 in coords:
            got[order[cid]] = (pc1, pc2)
        coord_err = max(coord_err, reference.coord_err(got, c_ref[:, :2]))
    return {
        "g_err": {"value": g_err, "limit": float(limits["g_err"])},
        "coord_err": {"value": coord_err, "limit": float(limits["coord_err"])},
        "analyses_compared": {"value": len(results), "limit": 1},
    }


def checks_pass(checks: dict) -> bool:
    return (checks["g_err"]["value"] <= checks["g_err"]["limit"]
            and checks["coord_err"]["value"] <= checks["coord_err"]["limit"]
            and checks["analyses_compared"]["value"]
            >= checks["analyses_compared"]["limit"])


# -- the run -----------------------------------------------------------------


def parse_args(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None, root: str = ROOT) -> int:
    args = parse_args(argv)
    cell = resolve_cell(root, args.workload)
    devices = require_devices(cell.chips)
    if devices is None:
        return 2
    from benchmark.peaks import peaks_for

    peaks = peaks_for(devices[0].device_kind)
    _say(f"compile cache: {use_compile_cache(root)}; JAX up at "
         f"{time.perf_counter() - T_START:.2f} s")
    clock = CompileCounter()

    cohort_cfg = cell.config["cohort"]
    generator = load_module(root, "cohorts", cohort_cfg["generator"])
    t0 = time.perf_counter()
    cohort = generator.generate(cohort_cfg, args.seed)
    _say(f"setup: cohort {cohort.n_samples} x {cohort.n_sites}, "
         f"{cohort.nnz} carriers, generated in "
         f"{time.perf_counter() - t0:.2f} s")
    from benchmark.cohortlib import write_light_mirror

    work_dir = tempfile.mkdtemp(prefix="benchmark-cohort-")
    try:
        cohort_dir = os.path.join(work_dir, "cohort")
        t0 = time.perf_counter()
        write_light_mirror(cohort, cohort_dir)
        _say(f"setup: mirror written in {time.perf_counter() - t0:.2f} s")
        return _measure(args, root, cell, cohort, cohort_dir, devices,
                        peaks, clock)
    finally:
        shutil.rmtree(work_dir, ignore_errors=True)


def _measure(args, root, cell, cohort, cohort_dir, devices, peaks,
             clock) -> int:
    import jax
    import numpy as np

    flags = list(cell.traffic["flags"])
    refs = cohort.references()
    c0, t0 = clock.count, time.perf_counter()
    analysis(cohort_dir, refs, flags)
    _say(f"setup: warm analysis done in {time.perf_counter() - t0:.2f} s, "
         f"{clock.count - c0} compiles ({clock.seconds:.2f} s compiling in "
         "set-up)")

    session = log_dir = None
    if args.trace:
        from spark_examples_tpu import obs

        log_dir = tempfile.mkdtemp(prefix="benchmark-trace-")
        session = obs.TelemetrySession(annotate_jax=True, xla_cost=False)
        session.__enter__()
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0  # no per-call Python events
        options.host_tracer_level = 2
        jax.profiler.start_trace(log_dir, profiler_options=options)
    room = keep_count(cohort.n_samples)
    routes0 = route_counts()
    io0 = host_io()
    compiles0 = clock.count
    setup_s = time.perf_counter() - T_START
    # The answers of the first ``room - 1`` analyses and of the last are
    # kept for the comparison. Each of the first starts its copy to the host
    # as soon as it is done, and every G leaves the device when the next
    # analysis is, so the window holds one extra G at most.
    results, attempted, failed, ends, last = [], 0, 0, [], None
    w0 = time.perf_counter()
    from benchmark.trace import WINDOW

    with jax.profiler.TraceAnnotation(WINDOW):
        while True:
            attempted += 1
            try:
                g, coords, rows = analysis(cohort_dir, refs, flags)
                if len(results) + (last is not None) < room - 1:
                    g.copy_to_host_async()
                if last is not None and len(results) < room - 1:
                    results.append((np.asarray(last[0]),) + last[1:])
                last = (g, coords, rows)
            except Exception as e:  # noqa: BLE001 — counted, run goes on
                failed += 1
                _say(f"analysis {attempted} failed: {e!r}")
            ends.append(time.perf_counter() - w0)
            if ends[-1] >= args.seconds:
                break
    window_s = ends[-1]
    if last is not None:
        results.append((np.asarray(last[0]),) + last[1:])
    del last
    done = attempted - failed  # analyses completed in the window
    io = {k: v - io0.get(k, 0) for k, v in host_io().items()}
    compiles = clock.count - compiles0
    routes = {k: v - routes0.get(k, 0.0) for k, v in route_counts().items()}

    spans = []
    if args.trace:
        jax.profiler.stop_trace()
        spans = _session_spans(session)
        session.__exit__(None, None, None)
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(jax.devices()),
        "memory_peak_bytes": peak_memory(devices[:cell.chips]),
    }
    _say(f"window: {done} analyses in {window_s:.3f} s, "
         f"{compiles} compiles; windows by route and scatter path: {routes}")
    _say(f"window: host reads and page faults {io}")
    _say("window: seconds per analysis " + " ".join(
        f"{b - a:.3f}" for a, b in zip([0.0] + ends[:-1], ends)))

    t_ref = time.perf_counter()
    checks = compare(cohort, results, int(cell.config["num_pc"]),
                     cell.config["limits"], devices=devices[:cell.chips])
    _say(f"reference: compared in {time.perf_counter() - t_ref:.2f} s")
    correct = failed == 0 and checks_pass(checks)

    if args.trace:
        from benchmark import trace as trace_mod

        path = trace_mod.find_xplane(log_dir)
        names = {s[0] for s in spans}
        trace_obj = trace_mod.load(path, names)
        shutil.rmtree(log_dir, ignore_errors=True)
        device["busy_s"] = trace_mod.busy_seconds(trace_obj)
        device["window_s"] = trace_obj.window_s
        observed = Observed(
            analyses=done, compiles=compiles,
            work=_work(cohort, cell), peaks=peaks, spans=spans,
            trace=trace_obj, chips=cell.chips,
        )
        metrics = {}
        for m in cell.per_layer:
            reader = load_module(root, "metrics", m["name"])
            value = reader.read(observed)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
            describe = getattr(reader, "describe", None)
            if describe is not None:
                _say(f"{m['name']}: {describe(observed)}")
        breakdown = {
            "device_ops": [[n, s] for n, s in trace_mod.top_ops(trace_obj)],
            "idle_gaps": [[n, s] for n, s in trace_mod.idle_gaps(trace_obj)],
        }
    else:
        values = {
            "analysis_s": window_s / max(done, 1),
            "setup_s": setup_s,
        }
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}
        breakdown = None

    line = {"correct": correct, "attempted": attempted, "failed": failed,
            "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["checks"] = checks
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    for name, c in checks.items():
        _say(f"check {name}: {c['value']!r} limit {c['limit']!r}")
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    # Skip interpreter teardown: nothing may print after the check lines.
    os._exit(code)
