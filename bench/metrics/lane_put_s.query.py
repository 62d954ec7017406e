"""Seconds per ``QueryEngine`` count pass in ``lane.put`` spans: the device
join lane's ``jnp.asarray`` of a box's arrays."""

from bench import spans

LAYER = "Lanes"
UNIT = "s/pass"
SOURCE = "program_span"
MOVES = "count_s"


def read(run):
    if not spans.named(run.spans, "lane.put"):
        return None
    return run.per_pass(spans.seconds_in(run.spans, "lane.put",
                                         "query.boxes"))
