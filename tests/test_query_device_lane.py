"""The device join lane (``query.device_join``): k-clique box counts as
XLA programs, pinned to brute force and to the scalar LFTJ.

* the lane's counts equal a brute-force k-subset count and
  ``core.queries.run_query`` for the triangle, the four-clique and the
  5-clique, boxed and unboxed, with the probe a binary search (the CPU
  branch) and a compare against the whole row (the TPU branch);
* ``QueryEngine`` on a TPU-like engine routes every non-empty four-clique
  box to the lane, and counts each box it cannot take (another pattern,
  a row over the lane's cap) in ``n_device_join_fallbacks``, exactly;
* a second pass over the same store compiles no new program.
"""

from itertools import combinations

import jax
import numpy as np
import pytest

from repro.core import TrieArray, orient_edges, run_query
from repro.data.graphs import graph500_edges, simplify_edges
from repro.query import QueryEngine, device_join, patterns
from repro.query.vectorized import VectorizedBoxJoin

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


def er_graph(n, p, seed):
    rng = np.random.default_rng(seed)
    src, dst = np.nonzero(np.triu(rng.random((n, n)) < p, k=1))
    return src.astype(np.int64), dst.astype(np.int64)


def g500(scale, seed):
    return simplify_edges(*graph500_edges(scale, edge_factor=8, seed=seed))


GRAPHS = {
    "er": lambda: er_graph(24, 0.45, 3),
    "g500-a": lambda: g500(7, 1),
    "g500-b": lambda: g500(7, 2),
}
# a budget per graph that cuts every k-clique plan into tens of boxes
BOXED = {"er": 170, "g500-a": 700, "g500-b": 700}


def brute_force_cliques(src, dst, k):
    """k-subsets of the simple undirected graph whose pairs are all edges."""
    adj = {}
    for u, v in zip(src.tolist(), dst.tolist()):
        if u != v:
            adj.setdefault(u, set()).add(v)
            adj.setdefault(v, set()).add(u)
    return sum(all(b in adj[a] for a, b in combinations(c, 2))
               for c in combinations(sorted(adj), k))


def scalar_lftj(q, src, dst):
    a, b = orient_edges(src, dst, "minmax")
    return run_query(q, q.head, {"E": TrieArray.from_edges(a, b)})


def lane_total(qe):
    """(Σ of the lane's box counts, boxes it ran) over ``qe``'s plan,
    every box joined with ``device="xla"`` whatever the engine routes."""
    _, fetch, build, _ = qe.box_stages("count")
    total = ran = 0
    for box in qe.plan().boxes:
        built = build(fetch(box)[0])
        if built is None:
            continue
        vj = VectorizedBoxJoin(built[1], qe.n, "count", device="xla")
        total += vj.run()
        ran += vj.used_device_join
    return total, ran


@pytest.fixture(params=[False, True], ids=["search", "compare_all"])
def probe(request, monkeypatch):
    monkeypatch.setattr(device_join, "COMPARE_ALL", request.param)
    return request.param


@pytest.mark.parametrize("k", [3, 4, 5])
@pytest.mark.parametrize("graph", sorted(GRAPHS))
def test_lane_counts_match_references(graph, k, probe):
    src, dst = GRAPHS[graph]()
    q = patterns.k_clique(k)
    want = scalar_lftj(q, src, dst)
    if graph == "er":
        assert brute_force_cliques(src, dst, k) == want
    for mem in (None, BOXED[graph]):
        qe = QueryEngine.from_graph(q, src, dst, mem_words=mem,
                                    use_pallas_kernels=False)
        got, ran = lane_total(qe)
        assert got == want, (graph, k, mem)
        assert ran > 0


def test_engine_routes_four_clique_boxes_to_the_lane():
    src, dst = GRAPHS["g500-a"]()
    q = patterns.four_clique()
    want = scalar_lftj(q, src, dst)
    qe = QueryEngine.from_graph(q, src, dst, mem_words=BOXED["g500-a"],
                                use_pallas_kernels=True)
    assert qe.count() == want
    s = qe.stats
    assert s.n_device_join_boxes > 1
    assert s.n_device_join_fallbacks == 0
    assert s.n_host_boxes == s.n_kernel_boxes == 0
    assert s.device_invocations >= 2 * s.n_device_join_boxes
    assert s.device_transfer_bytes > 0
    # off a TPU-like engine nothing changes: the staged host path
    host = QueryEngine.from_graph(q, src, dst, mem_words=BOXED["g500-a"],
                                  use_pallas_kernels=False)
    assert host.count() == want
    assert host.stats.n_device_join_boxes == 0
    assert host.stats.n_device_join_fallbacks == 0


def test_diamond_takes_the_staged_path_counted():
    src, dst = GRAPHS["er"]()
    q = patterns.diamond()
    want = scalar_lftj(q, src, dst)
    qe = QueryEngine.from_graph(q, src, dst, mem_words=BOXED["er"],
                                use_pallas_kernels=True)
    assert qe.count() == want
    assert qe.stats.n_device_join_boxes == 0
    assert qe.stats.n_device_join_fallbacks > 0
    assert qe.stats.n_device_join_fallbacks == \
        qe.stats.n_kernel_boxes + qe.stats.n_host_boxes


def test_row_over_the_cap_takes_the_staged_path_counted():
    """A hub whose out-row is wider than the lane's cap: the box runs
    staged, is counted, and the count stays exact."""
    cap = device_join.width_cap(4)
    hub = np.zeros(cap + 40, np.int64)
    leaves = np.arange(1, cap + 41, dtype=np.int64)
    rng = np.random.default_rng(5)
    pairs = rng.integers(1, cap + 41, size=(3000, 2))
    src = np.concatenate([hub, pairs[:, 0]])
    dst = np.concatenate([leaves, pairs[:, 1]])
    q = patterns.four_clique()
    want = scalar_lftj(q, src, dst)
    qe = QueryEngine.from_graph(q, src, dst, use_pallas_kernels=True)
    assert qe.count() == want > 0
    assert qe.stats.n_device_join_fallbacks == 1
    assert qe.stats.n_device_join_boxes == 0


def test_second_pass_compiles_nothing():
    src, dst = GRAPHS["g500-b"]()
    qe = QueryEngine.from_graph(patterns.four_clique(), src, dst,
                                mem_words=BOXED["g500-b"],
                                use_pallas_kernels=True)
    first = qe.count()
    compiled = []

    def on_event(event, duration, **_):
        if event == BACKEND_COMPILE:
            compiled.append(duration)

    jax.monitoring.register_event_duration_secs_listener(on_event)
    try:
        assert qe.count() == first
    finally:
        jax.monitoring.unregister_event_duration_listener(on_event)
    assert qe.stats.n_device_join_boxes > 1
    assert compiled == []


def test_width_cap_keeps_counts_in_int32():
    from math import comb

    for k in range(3, 7):
        w = device_join.width_cap(k)
        assert comb(w, k - 1) < 1 << 31
        assert w == device_join.MAX_WIDTH or comb(2 * w, k - 1) >= 1 << 31
    assert device_join.clique_size([(0, 1), (0, 2), (1, 2)], 3) == 3
    assert device_join.clique_size([(0, 1), (0, 2), (1, 3), (2, 3)], 4) \
        is None
    assert device_join.clique_size([(0, 1)], 2) is None
