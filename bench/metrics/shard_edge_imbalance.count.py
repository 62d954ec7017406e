"""The largest shard's edges over the mean shard's in a sharded count
pass (``EngineStats.shard_edges``): how evenly the box schedule spreads
the work over the chips. 1 is even."""

LAYER = "Sharding"
UNIT = "ratio"
SOURCE = "program_counter"
MOVES = "count_s"


def read(run):
    edges = getattr(run.stats, "shard_edges", None)
    if not edges or sum(edges) == 0:
        return None
    return max(edges) / (sum(edges) / len(edges))
