"""Seconds per sharded count pass in ``shard.run`` spans: building the
sharded program, its call and the host sum of the chips' partials."""

from bench import spans

LAYER = "Sharding"
UNIT = "s/pass"
SOURCE = "program_span"
MOVES = "count_s"


def read(run):
    if not spans.named(run.spans, "shard.run"):
        return None
    return run.per_pass(spans.seconds_in(run.spans, "shard.run",
                                         "engine.count"))
