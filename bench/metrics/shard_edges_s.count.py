"""Seconds per sharded count pass in ``shard.edges`` spans: each box's
in-box edges and backend, and any box counted on this host."""

from bench import spans

LAYER = "Sharding"
UNIT = "s/pass"
SOURCE = "program_span"
MOVES = "count_s"


def read(run):
    if not spans.named(run.spans, "shard.edges"):
        return None
    return run.per_pass(spans.seconds_in(run.spans, "shard.edges",
                                         "engine.count"))
