"""Device starved by the host feed, seconds per analysis: the time in the
traced window when the first device plane runs no op and the thread that
dispatches Gramian steps waits for its next input (a ``feed.wait`` span is
open). Idle intervals intersected with the union of the ``feed.wait``
spans, both on the profiler's clock and cut to the window."""

from benchmark import intervals

LAYER = "Gramian"
UNIT = "s"
MOVES = "analysis_s"
SOURCE = "device_trace"

WAIT = ("feed.wait",)


def read(obs):
    if obs.trace is None or not obs.trace.ops or not obs.analyses:
        return None
    waits = intervals.spans(obs.trace, WAIT)
    if not waits:
        return None
    starved = intervals.intersect(intervals.idle(obs.trace), waits)
    return intervals.seconds(starved) / obs.analyses


def describe(obs):
    if obs.trace is None or not obs.trace.ops or not obs.analyses:
        return "no device plane"
    idle = intervals.seconds(intervals.idle(obs.trace)) / obs.analyses
    waits = intervals.spans(obs.trace, WAIT)
    return (f"device idle {idle:.6g} s per analysis; {len(waits)} "
            f"separate {WAIT[0]} intervals in the window")
