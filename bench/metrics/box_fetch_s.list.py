"""Seconds per list pass in ``box.fetch`` spans: reading each box's
slice from the store and compacting it, on the prefetch thread."""

from bench import spans

LAYER = "Box pipeline"
UNIT = "s/pass"
SOURCE = "program_span"
MOVES = "list_s"


def read(run):
    if not spans.named(run.spans, "box.fetch"):
        return None
    return run.per_pass(spans.seconds_in(run.spans, "box.fetch",
                                         "engine.list"))
