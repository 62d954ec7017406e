"""Seconds per list pass in ``engine.list`` after its last
``box.compute`` span ends: concatenating the boxes' rows and the
canonical sort."""

from bench import spans

LAYER = "Entry"
UNIT = "s/pass"
SOURCE = "program_span"
MOVES = "list_s"


def read(run):
    return run.per_pass(spans.tail_after_last(run.spans, "box.compute",
                                              "engine.list"))
