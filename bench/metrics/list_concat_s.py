"""Seconds per list pass in ``list.concat`` spans: concatenating the
boxes' triangle rows after the last box."""

from bench import spans

LAYER = "Entry"
UNIT = "s/pass"
SOURCE = "program_span"
MOVES = "list_s"


def read(run):
    if not spans.named(run.spans, "list.concat"):
        return None
    return run.per_pass(spans.seconds_in(run.spans, "list.concat",
                                         "engine.list"))
