"""Seconds per list pass in ``engine.canonical`` spans: the canonical
sort of the listed rows."""

from bench import spans

LAYER = "Entry"
UNIT = "s/pass"
SOURCE = "program_span"
MOVES = "list_s"


def read(run):
    if not spans.named(run.spans, "engine.canonical"):
        return None
    return run.per_pass(spans.seconds_in(run.spans, "engine.canonical",
                                         "engine.list"))
