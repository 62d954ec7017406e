"""Seconds per list pass in ``lane.readout`` spans: reading each box's
triangles off its hit mask on the host (nonzeros and row gather)."""

from bench import spans

LAYER = "Lanes"
UNIT = "s/pass"
SOURCE = "program_span"
MOVES = "list_s"


def read(run):
    if not spans.named(run.spans, "lane.readout"):
        return None
    return run.per_pass(spans.seconds_in(run.spans, "lane.readout",
                                         "engine.list"))
