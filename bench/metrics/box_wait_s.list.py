"""Seconds per list pass on the calling thread inside ``engine.list``,
up to the end of its last box, that no ``box.compute`` span covers:
where the caller plans and waits on the prefetcher."""

from bench import spans

LAYER = "Box pipeline"
UNIT = "s/pass"
SOURCE = "program_span"
MOVES = "list_s"


def read(run):
    return run.per_pass(spans.wait_before_last(run.spans, "box.compute",
                                               "engine.list"))
