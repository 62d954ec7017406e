"""Seconds per sharded count pass in ``shard.slices`` spans: the LPT
box schedule and each chip's renumbered slice."""

from bench import spans

LAYER = "Sharding"
UNIT = "s/pass"
SOURCE = "program_span"
MOVES = "count_s"


def read(run):
    if not spans.named(run.spans, "shard.slices"):
        return None
    return run.per_pass(spans.seconds_in(run.spans, "shard.slices",
                                         "engine.count"))
