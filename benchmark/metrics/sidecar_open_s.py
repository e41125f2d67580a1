"""The CSR sidecar's open per analysis: the summed durations of the
program's ``ingest.sidecar_open`` spans (one per source, around the
sidecar's load and the CRC check of its members), divided by the
analyses in the window. Each analysis opens a fresh source, so this is
the load and check that one analysis waits for before its first
shard."""

LAYER = "host ingest"
UNIT = "s"
MOVES = "analysis_s"
SOURCE = "program_span"

SPAN = "ingest.sidecar_open"


def read(obs):
    opens = [b - a for name, a, b, _ in obs.spans if name == SPAN]
    if not opens or not obs.analyses:
        return None
    return sum(opens) / obs.analyses
