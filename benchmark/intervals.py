"""Interval sets on the trace's clock, for the readers that split the
device's idle time by what the host was doing.

Every set here is a sorted list of disjoint ``(start, end)`` intervals, as
:func:`benchmark.trace.union` returns them.
"""

from __future__ import annotations

from typing import Iterable, List

from benchmark.trace import Interval, Trace, busy, clip, union


def idle(trace: Trace) -> List[Interval]:
    """The window minus the first device plane's op intervals."""
    lo, hi = trace.window
    busy_iv = busy(trace, sorted(trace.ops)[0]) if trace.ops else []
    edges = [lo] + [x for iv in busy_iv for x in iv] + [hi]
    return [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]


def spans(trace: Trace, names: Iterable[str]) -> List[Interval]:
    """Union of the program spans with one of ``names``, cut to the
    window."""
    names = set(names)
    return union((a, b) for n, a, b in clip(trace.spans, trace.window)
                 if n in names)


def intersect(xs: List[Interval], ys: List[Interval]) -> List[Interval]:
    out, i, j = [], 0, 0
    while i < len(xs) and j < len(ys):
        a = max(xs[i][0], ys[j][0])
        b = min(xs[i][1], ys[j][1])
        if b > a:
            out.append((a, b))
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return out


def subtract(xs: List[Interval], ys: List[Interval]) -> List[Interval]:
    """``xs`` with every point of ``ys`` taken out."""
    out = []
    j = 0
    for a, b in xs:
        while j < len(ys) and ys[j][1] <= a:
            j += 1
        k = j
        while k < len(ys) and ys[k][0] < b:
            if ys[k][0] > a:
                out.append((a, ys[k][0]))
            a = max(a, ys[k][1])
            k += 1
        if b > a:
            out.append((a, b))
    return out


def seconds(xs: List[Interval]) -> float:
    return sum(b - a for a, b in xs)
