"""Seconds per ``QueryEngine`` count pass in ``box.fetch`` spans: each
box's store reads and atom slices, on the prefetch thread."""

from bench import spans

LAYER = "Box pipeline"
UNIT = "s/pass"
SOURCE = "program_span"
MOVES = "count_s"


def read(run):
    if not spans.named(run.spans, "box.fetch"):
        return None
    return run.per_pass(spans.seconds_in(run.spans, "box.fetch",
                                         "query.boxes"))
