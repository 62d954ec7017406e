"""Plain references that decide ``correct``: exact triangle counts and a
check of listed rows, in scipy and numpy over the raw generated edges.

Copied from the program's ``chip_smoke.py`` (``reference_dag``,
``reference_triangles``, ``check_triangle_rows``) and kept here so that
the yardstick holds still while the program changes. Nothing here
imports the program. The row check returns counts of faults, each
compared with its limit by the harness, instead of raising.
"""

from __future__ import annotations

from typing import Dict

import numpy as np


def reference_dag(src: np.ndarray, dst: np.ndarray):
    """Simple undirected graph as a scipy CSR DAG, each edge pointing from
    its lower-degree end (ties by id)."""
    import scipy.sparse as sp

    n = int(max(src.max(initial=0), dst.max(initial=0))) + 1
    keep = src != dst
    lo = np.minimum(src[keep], dst[keep])
    hi = np.maximum(src[keep], dst[keep])
    key = np.unique(lo * n + hi)
    a, b = key // n, key % n
    deg = np.bincount(a, minlength=n) + np.bincount(b, minlength=n)
    flip = (deg[a] > deg[b]) | ((deg[a] == deg[b]) & (a > b))
    u, v = np.where(flip, b, a), np.where(flip, a, b)
    m = sp.csr_matrix((np.ones(len(u), np.int32), (u, v)), shape=(n, n))
    m.sort_indices()
    return m


def reference_triangles(dag) -> int:
    """Σ over DAG edges (u, v) of |N+(u) ∩ N+(v)|, in row blocks."""
    total, n, step = 0, dag.shape[0], 1 << 16
    for lo in range(0, n, step):
        blk = dag[lo:lo + step]
        total += int((blk @ dag).multiply(blk).sum(dtype=np.int64))
    return total


def row_faults(rows: np.ndarray, dag, want: int,
               step: int = 1 << 24) -> Dict[str, int]:
    """How far ``rows`` is from listing every triangle once in
    ``TriangleEngine.list()``'s canonical form (each row ascending, rows
    strictly ascending, which also makes them unique).

    Returns ``row_count_gap`` (|rows − want|), ``rows_out_of_order``
    (rows not ascending, plus neighbours not strictly ascending) and
    ``pairs_not_edges`` (vertex pairs of rows that are no graph edge).
    All three are 0 exactly when the listing is right. Rows are checked
    in ``step``-row chunks to bound host memory; each chunk's pair keys
    are sorted before they are looked up, which makes the lookup
    sequential."""
    rows = np.asarray(rows)
    if rows.ndim != 2 or rows.shape[1] != 3:
        return {"row_count_gap": abs(len(rows) - want),
                "rows_out_of_order": len(rows),
                "pairs_not_edges": 3 * len(rows)}
    n = dag.shape[0]
    coo = dag.tocoo()
    edges = np.sort(np.minimum(coo.row, coo.col).astype(np.int64) * n
                    + np.maximum(coo.row, coo.col))
    out_of_order = not_edges = 0
    prev = None
    for s in range(0, len(rows), step):
        r = np.asarray(rows[s:s + step], dtype=np.int64)
        asc = (r[:, 0] < r[:, 1]) & (r[:, 1] < r[:, 2])
        out_of_order += int(np.count_nonzero(~asc))
        key = (r[:, 0] * n + r[:, 1]) * n + r[:, 2]
        out_of_order += int(np.count_nonzero(key[1:] <= key[:-1]))
        if prev is not None and len(key):
            out_of_order += int(key[0] <= prev)
        if len(key):
            prev = key[-1]
        for i, j in ((0, 1), (0, 2), (1, 2)):
            q = np.sort(np.minimum(r[:, i], r[:, j]) * n
                        + np.maximum(r[:, i], r[:, j]))
            if len(edges) == 0:
                not_edges += len(q)
                continue
            pos = np.minimum(np.searchsorted(edges, q), len(edges) - 1)
            not_edges += int(np.count_nonzero(edges[pos] != q))
    return {"row_count_gap": abs(len(rows) - want),
            "rows_out_of_order": out_of_order,
            "pairs_not_edges": not_edges}
