"""Share of the traced window in which no op ran on the device: 1 minus
the union of the device's op intervals over the window."""

LAYER = "device"
UNIT = "%"
MOVES = "analysis_s"
SOURCE = "device_trace"


def read(obs):
    from benchmark.trace import busy_seconds

    if obs.trace is None or not obs.trace.ops:
        return None
    return 100.0 * (1.0 - busy_seconds(obs.trace) / obs.trace.window_s)
