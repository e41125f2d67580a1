"""Reduction of a profiler trace to what the per-layer metrics read.

The JAX profiler writes ``<dir>/plugins/profile/<run>/<host>.xplane.pb``;
:func:`load` reads it with ``jax.profiler.ProfileData`` and keeps:

- per device plane (``/device:...``), the op events of its ``XLA Ops``
  line and the executable events of its ``XLA Modules`` line;
- the traced window, the host annotation the harness opens around the
  measured analyses (:data:`WINDOW`);
- the program's own spans, which the telemetry session mirrors into the
  trace as host annotations (only names the caller lists are kept).

Times are seconds on the trace's clock. Every reduction clips to the
window.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Tuple

WINDOW = "benchmark.window"

Interval = Tuple[float, float]


@dataclass
class Trace:
    window: Interval
    ops: Dict[str, List[Tuple[str, float, float]]] = field(default_factory=dict)
    modules: Dict[str, List[Tuple[str, float, float]]] = field(default_factory=dict)
    spans: List[Tuple[str, float, float]] = field(default_factory=list)

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]


def find_xplane(log_dir: str) -> Optional[str]:
    paths = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return paths[-1] if paths else None


def load(path: str, span_names: Iterable[str] = ()) -> Trace:
    from jax.profiler import ProfileData

    keep = set(span_names)
    window = None
    ops: Dict[str, list] = {}
    modules: Dict[str, list] = {}
    spans = []
    for plane in ProfileData.from_file(path).planes:
        device = plane.name.startswith("/device:")
        for line in plane.lines:
            for ev in line.events:
                start = ev.start_ns * 1e-9
                end = start + ev.duration_ns * 1e-9
                if device and line.name == "XLA Ops":
                    ops.setdefault(plane.name, []).append((ev.name, start, end))
                elif device and line.name == "XLA Modules":
                    modules.setdefault(plane.name, []).append(
                        (ev.name, start, end))
                elif not device and ev.name == WINDOW:
                    window = (start, end)
                elif not device and ev.name in keep:
                    spans.append((ev.name, start, end))
    if window is None:
        raise ValueError(f"{path}: no {WINDOW!r} annotation in the trace")
    return Trace(window=window, ops=ops, modules=modules, spans=spans)


def union(intervals: Iterable[Interval]) -> List[Interval]:
    merged: List[List[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def clip(events, window):
    """``(name, start, end)`` events cut to the window; those outside it
    dropped."""
    lo, hi = window
    return [(n, max(a, lo), min(b, hi)) for n, a, b in events
            if b > lo and a < hi]


def busy(trace: Trace, plane: str) -> List[Interval]:
    """Union of one device plane's op intervals, in the window."""
    return union((a, b) for _, a, b in clip(trace.ops[plane], trace.window))


def busy_seconds(trace: Trace) -> float:
    """Seconds in which some op ran, averaged over the device planes."""
    if not trace.ops:
        return 0.0
    return sum(b - a for plane in trace.ops for a, b in busy(trace, plane)
               ) / len(trace.ops)


def _module_name(name: str) -> str:
    """``jit__finish_jit(42)`` -> ``jit__finish_jit``."""
    return re.sub(r"\(\d+\)$", "", name)


def _op_name(name: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``."""
    return name.split(" = ", 1)[0].lstrip("%")


def module_seconds(trace: Trace, patterns: Iterable[str]) -> float:
    """Device seconds of the executables whose name contains a pattern,
    summed over the events in the window and averaged over devices."""
    patterns = tuple(patterns)
    if not trace.modules:
        return 0.0
    total = 0.0
    for events in trace.modules.values():
        for name, a, b in clip(events, trace.window):
            if any(p in name for p in patterns):
                total += b - a
    return total / len(trace.modules)


def top_ops(trace: Trace, limit: int = 10) -> List[Tuple[str, float]]:
    """Device seconds per op, named ``<executable>/<op>``, most first.
    An op that holds others (a ``while``) counts the time of its body."""
    if not trace.ops:
        return []
    plane = sorted(trace.ops)[0]
    mods = sorted(clip(trace.modules.get(plane, []), trace.window),
                  key=lambda e: e[1])
    starts = [a for _, a, _ in mods]
    totals: Dict[str, float] = {}
    for name, a, b in clip(trace.ops[plane], trace.window):
        i = bisect.bisect_right(starts, a) - 1
        owner = (_module_name(mods[i][0])
                 if i >= 0 and mods[i][2] >= a else "?")
        key = f"{owner}/{_op_name(name)}"
        totals[key] = totals.get(key, 0.0) + (b - a)
    return sorted(totals.items(), key=lambda kv: -kv[1])[:limit]


def idle_gaps(trace: Trace, limit: int = 10) -> List[Tuple[str, float]]:
    """Idle device time in the window (first device plane), summed by the
    innermost program span open on the host at the middle of each gap,
    most first."""
    busy_iv = busy(trace, sorted(trace.ops)[0]) if trace.ops else []
    lo, hi = trace.window
    edges = [lo] + [x for iv in busy_iv for x in iv] + [hi]
    totals: Dict[str, float] = {}
    for a, b in zip(edges[0::2], edges[1::2]):
        if b <= a:
            continue
        mid = 0.5 * (a + b)
        open_spans = [(s, n) for n, s, e in trace.spans if s <= mid < e]
        label = max(open_spans)[1] if open_spans else "(no program span)"
        totals[label] = totals.get(label, 0.0) + (b - a)
    return sorted(totals.items(), key=lambda kv: -kv[1])[:limit]
