"""The paper's RAND set (arXiv:1501.06689 section 6): endpoints drawn
uniformly, self loops and duplicate edges dropped.

A copy of the program's ``data.graphs.random_graph``, kept here so that
the benchmark's inputs stay fixed while the program changes. The
configuration gives ``n_nodes``, ``n_edges`` and ``structure_seed``.
"""

from __future__ import annotations

import numpy as np


def random_edges(n_nodes: int, n_edges: int, structure_seed: int):
    """The paper's RAND set: uniform endpoints, then simplified (self
    loops and duplicate undirected edges dropped)."""
    rng = np.random.default_rng(structure_seed)
    src = rng.integers(0, n_nodes, n_edges, dtype=np.int64)
    dst = rng.integers(0, n_nodes, n_edges, dtype=np.int64)
    keep = src != dst
    lo = np.minimum(src[keep], dst[keep])
    hi = np.maximum(src[keep], dst[keep])
    key = np.unique(lo * n_nodes + hi)
    return key // n_nodes, key % n_nodes


def edges(cfg: dict):
    """The raw edges and the vertex count of configuration ``cfg``."""
    return (random_edges(cfg["n_nodes"], cfg["n_edges"],
                         cfg["structure_seed"]), cfg["n_nodes"])
