"""Seconds per list pass in ``lane.pad`` spans: building each box's
padded neighbor matrix and bucketing its edges on the host."""

from bench import spans

LAYER = "Lanes"
UNIT = "s/pass"
SOURCE = "program_span"
MOVES = "list_s"


def read(run):
    if not spans.named(run.spans, "lane.pad"):
        return None
    return run.per_pass(spans.seconds_in(run.spans, "lane.pad",
                                         "engine.list"))
