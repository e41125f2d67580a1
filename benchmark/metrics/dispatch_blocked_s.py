"""Device idle while the host dispatches, seconds per analysis: the time
in the traced window when the first device plane runs no op, a Gramian
step's dispatch span (``gramian.accumulate``, ``gramian.sparse.window``)
is open, and no ``feed.wait`` is. All on the profiler's clock, cut to the
window."""

from benchmark import intervals

LAYER = "Gramian"
UNIT = "s"
MOVES = "analysis_s"
SOURCE = "device_trace"

DISPATCH = ("gramian.accumulate", "gramian.sparse.window")
WAIT = ("feed.wait",)


def read(obs):
    if obs.trace is None or not obs.trace.ops or not obs.analyses:
        return None
    dispatch = intervals.spans(obs.trace, DISPATCH)
    if not dispatch:
        return None
    blocked = intervals.subtract(
        intervals.intersect(intervals.idle(obs.trace), dispatch),
        intervals.spans(obs.trace, WAIT))
    return intervals.seconds(blocked) / obs.analyses
