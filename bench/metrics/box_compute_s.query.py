"""Seconds per ``QueryEngine`` count pass in ``box.compute`` spans: each
box's join, on the device join lane or the staged path."""

from bench import spans

LAYER = "Lanes"
UNIT = "s/pass"
SOURCE = "program_span"
MOVES = "count_s"


def read(run):
    if not spans.named(run.spans, "box.compute"):
        return None
    return run.per_pass(spans.seconds_in(run.spans, "box.compute",
                                         "query.boxes"))
