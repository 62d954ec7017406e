"""One pass: a whole-graph four-clique count through ``QueryEngine``
(``patterns.four_clique()``) over the store the program's own ingest
built, ending in a host int. Every pass's count must equal the
reference's.

The cell measures the four-clique's box joins on the device. A program
whose ``QueryStats`` has no ``n_device_join_boxes`` counter has no device
join lane: its four-clique pass runs on the host alone, and a traced run
would find no device op in its window. ``open`` refuses such a program
at once, before the store is built, so the run ends with no result.

The reference is a copy of the program's ``chip_smoke.py``
``reference_four_cliques`` in scipy, kept here so that the yardstick holds
still while the program changes; it imports nothing of the program."""

import numpy as np


def reference_four_cliques(dag) -> int:
    """Σ over vertices u of the triangles among N+(u), over the DAG the
    benchmark's ``reference.reference_dag`` builds."""
    total = 0
    ip, ix = dag.indptr, dag.indices
    for u in np.flatnonzero(np.diff(ip) >= 3):
        nb = ix[ip[u]:ip[u + 1]]
        sub = dag[nb][:, nb]
        if sub.nnz >= 3:
            total += int((sub @ sub).multiply(sub).sum(dtype=np.int64))
    return total


def open(setup):
    from repro.query import QueryEngine, QueryStats, patterns

    if not hasattr(QueryStats, "n_device_join_boxes"):
        raise RuntimeError("this program has no device join lane (QueryStats "
                           "has no n_device_join_boxes): its four-clique "
                           "count would leave the device idle")
    setup.triangle_engine()                 # writes the store
    return QueryEngine(patterns.four_clique(), store=setup.store_path,
                       mem_words=setup.cfg["mem_words"],
                       tracer=setup.tracer, **setup.options)


def run(eng):
    return eng.count()


def check(results, dag):
    want = reference_four_cliques(dag)
    gaps = [abs(int(c) - want) for c in results]
    return {"count_gap": (max(gaps), 0)}, sum(g != 0 for g in gaps)
