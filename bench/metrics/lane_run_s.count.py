"""Seconds per count pass in ``lane.run`` spans: each box's program
call up to its result on the host (transfer, kernel and readback)."""

from bench import spans

LAYER = "Lanes"
UNIT = "s/pass"
SOURCE = "program_span"
MOVES = "count_s"


def read(run):
    if not spans.named(run.spans, "lane.run"):
        return None
    return run.per_pass(spans.seconds_in(run.spans, "lane.run",
                                         "engine.count"))
