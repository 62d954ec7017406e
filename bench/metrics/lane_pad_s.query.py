"""Seconds per ``QueryEngine`` count pass in ``lane.pad`` spans: the device
join lane's host layout of a box (flat CSR, loc entries, width classes)."""

from bench import spans

LAYER = "Lanes"
UNIT = "s/pass"
SOURCE = "program_span"
MOVES = "count_s"


def read(run):
    if not spans.named(run.spans, "lane.pad"):
        return None
    return run.per_pass(spans.seconds_in(run.spans, "lane.pad",
                                         "query.boxes"))
