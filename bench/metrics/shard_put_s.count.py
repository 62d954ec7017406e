"""Seconds per sharded count pass in ``shard.put`` spans: the
host-to-device copies of the four sharded arrays."""

from bench import spans

LAYER = "Sharding"
UNIT = "s/pass"
SOURCE = "program_span"
MOVES = "count_s"


def read(run):
    if not spans.named(run.spans, "shard.put"):
        return None
    return run.per_pass(spans.seconds_in(run.spans, "shard.put",
                                         "engine.count"))
