"""Graph500 Kronecker graph (Graph500 specification section 3).

A copy of the program's ``data.graphs.graph500_edges``, kept here so
that the benchmark's inputs stay fixed while the program changes. The
configuration gives ``scale``, ``edge_factor``, ``initiator`` (A, B, C)
and ``structure_seed``.
"""

from __future__ import annotations

import numpy as np


def graph500_edges(scale: int, edge_factor: int, initiator,
                   structure_seed: int):
    """Raw Graph500 Kronecker edge list (Graph500 specification §3):
    2^scale vertices, ``edge_factor``·2^scale generated edges, initiator
    A/B/C. Self loops and duplicates are kept, as the generator emits
    them."""
    rng = np.random.default_rng(structure_seed)
    m = edge_factor << scale
    a, b, c = initiator
    ab, c_norm, a_norm = a + b, c / (1.0 - (a + b)), a / (a + b)
    src = np.zeros(m, dtype=np.int64)
    dst = np.zeros(m, dtype=np.int64)
    for bit in range(scale):
        ii = rng.random(m) > ab
        jj = rng.random(m) > np.where(ii, c_norm, a_norm)
        src |= ii.astype(np.int64) << bit
        dst |= jj.astype(np.int64) << bit
    return src, dst


def edges(cfg: dict):
    """The raw edges and the vertex count of configuration ``cfg``."""
    return (graph500_edges(cfg["scale"], cfg["edge_factor"],
                           cfg["initiator"], cfg["structure_seed"]),
            1 << cfg["scale"])
