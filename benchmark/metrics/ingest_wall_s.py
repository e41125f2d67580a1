"""Host ingest wall time per analysis: the union over threads of the
program's ``ingest.*`` spans (``ingest.slice``, ``ingest.build``,
``ingest.pack``, ``ingest.put``, ``ingest.fetch``, ``ingest.stream``),
divided by the analyses in the window."""

LAYER = "host ingest"
UNIT = "s"
MOVES = "analysis_s"
SOURCE = "program_span"

PREFIX = "ingest."


def read(obs):
    intervals = sorted((a, b) for name, a, b, _ in obs.spans
                       if name.startswith(PREFIX))
    if not intervals or not obs.analyses:
        return None
    total, cur_a, cur_b = 0.0, intervals[0][0], intervals[0][1]
    for a, b in intervals[1:]:
        if a <= cur_b:
            cur_b = max(cur_b, b)
        else:
            total += cur_b - cur_a
            cur_a, cur_b = a, b
    total += cur_b - cur_a
    return total / obs.analyses
