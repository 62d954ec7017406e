"""Seconds per count pass in ``box.wait`` spans: the caller blocked on
the prefetcher for its next box."""

from bench import spans

LAYER = "Box pipeline"
UNIT = "s/pass"
SOURCE = "program_span"
MOVES = "count_s"


def read(run):
    if not spans.named(run.spans, "box.wait"):
        return None
    return run.per_pass(spans.seconds_in(run.spans, "box.wait",
                                         "engine.count"))
