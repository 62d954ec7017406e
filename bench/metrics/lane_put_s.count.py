"""Seconds per count pass in ``lane.put`` spans: the ``jnp.asarray``
host-to-device copies of each box's padded matrix and edge lists."""

from bench import spans

LAYER = "Lanes"
UNIT = "s/pass"
SOURCE = "program_span"
MOVES = "count_s"


def read(run):
    if not spans.named(run.spans, "lane.put"):
        return None
    return run.per_pass(spans.seconds_in(run.spans, "lane.put",
                                         "engine.count"))
