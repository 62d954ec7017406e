"""Programs JAX compiled or loaded from its cache inside the timed
window of a ``QueryEngine`` cell, from JAX's own compile events; it
should read 0."""

LAYER = "Lanes / JIT"
UNIT = "programs"
SOURCE = "program_counter"
MOVES = "count_s"


def read(run):
    return run.compiles
