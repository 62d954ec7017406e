"""Seconds per ``QueryEngine`` count pass in ``join.host`` spans: the box
join's work left on the host (the depth-0 key intersection, and the
staged frontier path of boxes outside the device join lane, with any
``kernels/intersect`` calls it makes)."""

from bench import spans

LAYER = "Lanes"
UNIT = "s/pass"
SOURCE = "program_span"
MOVES = "count_s"


def read(run):
    if not spans.named(run.spans, "join.host"):
        return None
    return run.per_pass(spans.seconds_in(run.spans, "join.host",
                                         "query.boxes"))
