"""Seconds per ``QueryEngine`` count pass in ``lane.run`` spans: the device
join lane's program calls for a box, to the per-vertex counts on the
host."""

from bench import spans

LAYER = "Lanes"
UNIT = "s/pass"
SOURCE = "program_span"
MOVES = "count_s"


def read(run):
    if not spans.named(run.spans, "lane.run"):
        return None
    return run.per_pass(spans.seconds_in(run.spans, "lane.run",
                                         "query.boxes"))
