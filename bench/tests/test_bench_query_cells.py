"""The ``QueryEngine`` cells: ``g500-s18.query`` (triangle) and
``g500-s15.k4`` (four-clique).

* the four-clique entry's own reference equals a brute-force count;
* each entry's ``check`` fails a pass with a planted wrong count;
* the ``.query`` readers read their spans inside ``query.boxes`` per
  pass, and nothing where the program opened none;
* both cells run end to end through the harness at a tiny size on the
  CPU, correct, traced and untraced;
* the four-clique entry refuses, before any ingest, a program with no
  device join lane.
"""

import json
from itertools import combinations
from types import SimpleNamespace

import bench_helpers
import numpy as np
import pytest

from bench import reference, spans as S
from bench.harness import RunView, Spec
from bench.trace_reduce import TraceSummary

CALLER, PRODUCER = 1, 2
CELLS = ("g500-s18.query", "g500-s15.k4")


def spec():
    return Spec(bench_helpers.ROOT)


def er_edges(n, p, seed):
    rng = np.random.default_rng(seed)
    src, dst = np.nonzero(np.triu(rng.random((n, n)) < p, k=1))
    return src.astype(np.int64), dst.astype(np.int64)


def brute_force_k4(src, dst):
    adj = set(zip(src.tolist(), dst.tolist()))
    adj |= {(b, a) for a, b in adj}
    nodes = sorted({x for e in adj for x in e})
    return sum(all((a, b) in adj for a, b in combinations(c, 2))
               for c in combinations(nodes, 4))


@pytest.mark.parametrize("n,p,seed", [(18, 0.5, 1), (22, 0.4, 2),
                                      (16, 0.8, 3)])
def test_k4_reference_equals_brute_force(n, p, seed):
    src, dst = er_edges(n, p, seed)
    dag = reference.reference_dag(src, dst)
    want = brute_force_k4(src, dst)
    assert want > 0
    assert spec().entry("k4").reference_four_cliques(dag) == want


def test_checks_fail_planted_wrong_counts():
    src, dst = er_edges(20, 0.5, 4)
    dag = reference.reference_dag(src, dst)
    tri = reference.reference_triangles(dag)
    k4 = spec().entry("k4")
    quads = k4.reference_four_cliques(dag)
    assert tri != quads

    query = spec().entry("query")
    assert query.check([tri, tri], dag) == ({"count_gap": (0, 0)}, 0)
    assert query.check([tri, tri + 1], dag) == ({"count_gap": (1, 0)}, 1)
    assert query.check([tri - 1, tri + 1], dag)[1] == 2

    assert k4.check([quads, quads], dag) == ({"count_gap": (0, 0)}, 0)
    assert k4.check([quads + 1], dag) == ({"count_gap": (1, 0)}, 1)
    checks, failed = k4.check([quads, tri], dag)   # the triangle count
    assert failed == 1 and checks["count_gap"] == (abs(tri - quads), 0)


def snapshot(records):
    ev = []
    for sid, (name, tid, s, e) in enumerate(records, 1):
        ev.append({"ph": "B", "name": name, "ts": s * 1e6, "tid": tid,
                   "sid": sid, "parent": None, "lane": None})
        ev.append({"ph": "E", "ts": e * 1e6, "tid": tid, "sid": sid,
                   "lane": None})
    ev.sort(key=lambda r: r["ts"])
    return ev


def query_pass(t, outer="query.boxes"):
    """A pass from ``t`` to ``t + 10``: two boxes on the device join lane
    and one staged, the prefetch thread reading beside them."""
    recs = [(outer, CALLER, t, t + 10.0)]
    for b in range(2):
        s = t + 1.0 + 3.0 * b
        recs += [("box.compute", CALLER, s, s + 2.0),
                 ("join.host", CALLER, s, s + 0.25),
                 ("lane.pad", CALLER, s + 0.25, s + 0.75),
                 ("lane.put", CALLER, s + 0.75, s + 1.0),
                 ("lane.run", CALLER, s + 1.0, s + 2.0),
                 ("box.fetch", PRODUCER, s + 2.0, s + 2.5)]
    recs += [("box.compute", CALLER, t + 7.0, t + 9.0),
             ("join.host", CALLER, t + 7.0, t + 7.25),
             ("join.host", CALLER, t + 7.25, t + 9.0)]
    return recs


TWO_PASSES = query_pass(0.0) + query_pass(20.0)

# reader -> seconds per pass on TWO_PASSES
SPAN_READERS = {
    "box_compute_s.query": 6.0,
    "box_fetch_s.query": 1.0,
    "join_host_s.query": 2.5,
    "lane_pad_s.query": 1.0,
    "lane_put_s.query": 0.5,
    "lane_run_s.query": 2.0,
}


def view(records, passes=2, transfer_bytes=0, trace=None, peaks=None,
         graph_words=0):
    return RunView(passes=passes, spans=S.spans_from_snapshot(
        snapshot(records)), stats=SimpleNamespace(
            device_transfer_bytes=transfer_bytes), compiles=0,
        trace=trace, graph_words=graph_words, peaks=peaks or {})


@pytest.mark.parametrize("name", sorted(SPAN_READERS))
def test_span_reader_reads_seconds_per_pass(name):
    got = spec().metric_reader(name).read(view(TWO_PASSES))
    assert got == pytest.approx(SPAN_READERS[name])


@pytest.mark.parametrize("name", sorted(SPAN_READERS))
def test_span_reader_reads_nothing_without_its_spans(name):
    bare = [r for r in TWO_PASSES if r[0] == "query.boxes"]
    assert spec().metric_reader(name).read(view(bare)) is None


@pytest.mark.parametrize("name", sorted(SPAN_READERS))
def test_span_reader_counts_only_query_passes(name):
    """Spans of a ``TriangleEngine`` pass (inside ``engine.count``) are no
    ``QueryEngine`` pass's."""
    other = query_pass(0.0, "engine.count") + query_pass(20.0,
                                                         "engine.count")
    assert spec().metric_reader(name).read(view(other)) in (None, 0.0)


def test_lane_and_host_spans_cover_box_compute():
    run = view(TWO_PASSES)
    parts = sum(spec().metric_reader(f"{p}.query").read(run)
                for p in ("lane_pad_s", "lane_put_s", "lane_run_s",
                          "join_host_s"))
    assert parts == pytest.approx(
        spec().metric_reader("box_compute_s.query").read(run))


def test_counter_and_trace_readers():
    read = {n: spec().metric_reader(n).read for n in
            ("lane_put_mb.query", "compiles_in_window.query",
             "device_idle_share.query", "pass_roofline.query")}
    assert read["lane_put_mb.query"](view([], transfer_bytes=0)) is None
    assert read["lane_put_mb.query"](
        view([], transfer_bytes=3_500_000)) == pytest.approx(3.5)
    assert read["compiles_in_window.query"](view([])) == 0
    assert read["device_idle_share.query"](view([])) is None
    assert read["pass_roofline.query"](view([])) is None
    trace = TraceSummary(window_s=40.0, busy_s=10.0, busy_by_device={0: 10.})
    peaks = {"hbm_bytes_per_s": 1e9}
    assert read["device_idle_share.query"](view([], trace=trace)) \
        == pytest.approx(75.0)
    run = view([], passes=5, trace=trace, peaks=peaks, graph_words=250_000)
    # 1 MB at 1 GB/s = 1 ms of a 2 s busy pass
    assert read["pass_roofline.query"](run) == pytest.approx(0.05)
    idle = TraceSummary(window_s=40.0, busy_s=0.0, busy_by_device={0: 0.})
    assert read["pass_roofline.query"](
        view([], trace=idle, peaks=peaks, graph_words=1)) is None


def test_query_metrics_list_only_the_query_cells():
    doc = spec().doc
    query = [m for m in doc["per_layer"] if m["name"].endswith(".query")]
    assert len(query) == 10
    assert all(m["workloads"] == list(CELLS) for m in query)
    assert all(c not in m["workloads"] for m in doc["per_layer"]
               if m not in query for c in CELLS)
    count_s = [m for m in doc["end_to_end"] if m["name"] == "count_s"][0]
    assert set(CELLS) <= set(count_s["workloads"])


@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_end_to_end_tiny(tmp_path, cell):
    root = bench_helpers.tiny_root(tmp_path)
    path = root / "bench" / "configs" / "g500-s15.json"
    cfg = json.loads(path.read_text())
    cfg.update(scale=8, mem_words=1 << 11)
    path.write_text(json.dumps(cfg))
    plain = bench_helpers.run_tiny(root, cell)
    assert plain.correct and plain.failed == 0
    assert plain.checks["count_gap"] == {"value": 0, "limit": 0}
    assert set(plain.metrics) == {"count_s", "setup_s"}
    traced = bench_helpers.run_tiny(root, cell, trace=True)
    assert traced.correct
    for name in ("box_compute_s.query", "box_fetch_s.query",
                 "join_host_s.query", "compiles_in_window.query"):
        assert name in traced.metrics, name


def test_k4_refuses_a_program_without_the_device_join_lane(monkeypatch):
    from repro.query import QueryStats

    built = []
    setup = SimpleNamespace(triangle_engine=lambda: built.append(1))
    monkeypatch.delattr(QueryStats, "n_device_join_boxes")
    with pytest.raises(RuntimeError, match="no device join lane"):
        spec().entry("k4").open(setup)
    assert built == []
