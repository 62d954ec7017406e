"""Seconds per count pass in ``box.compute`` spans: each box's host
preparation, transfer, device work and readout on its lane."""

from bench import spans

LAYER = "Lanes"
UNIT = "s/pass"
SOURCE = "program_span"
MOVES = "count_s"


def read(run):
    if not spans.named(run.spans, "box.compute"):
        return None
    return run.per_pass(spans.seconds_in(run.spans, "box.compute",
                                         "engine.count"))
