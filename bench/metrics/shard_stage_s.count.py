"""Seconds per sharded count pass in ``shard.stage`` spans: staging
the store through host memory for the sharded path (one full read)."""

from bench import spans

LAYER = "Sharding"
UNIT = "s/pass"
SOURCE = "program_span"
MOVES = "count_s"


def read(run):
    if not spans.named(run.spans, "shard.stage"):
        return None
    return run.per_pass(spans.seconds_in(run.spans, "shard.stage",
                                         "engine.count"))
