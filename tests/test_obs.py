"""Observability layer (PR 10): tracer spans + Chrome export, the
cross-layer metrics registry, and the contracts the instrumentation must
keep.

Two properties anchor everything:

* **Traced-off identity.** Attaching a ``Tracer``/``MetricsRegistry``
  must never change execution: counts, listings and measured
  ``block_reads`` are byte-identical traced-on vs traced-off.
* **Exact-sum adoption.** The registry mirrors the existing ledgers; the
  per-tag ``io.*`` series (including the ``_untagged`` residual) must
  sum to the raw ``BlockDevice`` globals field by field, and the
  per-tenant ``cache.*`` series (including ``_shared``) to the raw
  ``SharedSliceCache`` globals — property-checked over random served
  query mixes.
"""

import json
import threading

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.engine import EngineStats, TriangleEngine
from repro.core.executor import merge_queue_telemetry
from repro.data.graphs import random_graph, rmat_graph
from repro.obs import (MetricsRegistry, Tracer, default_registry,
                       set_default_registry, wrap_stage)
from repro.query import QueryEngine
from repro.query.patterns import PATTERNS
from repro.serve import Server

GRAPH = rmat_graph(512, 6000, seed=21)
SMALL = random_graph(200, 1500, seed=7)

IO_FIELDS = ("block_reads", "block_writes", "word_reads", "probes",
             "cache_served_words")
CACHE_FIELDS = ("hits", "misses", "hit_words", "miss_words",
                "passthrough_words")


def canon(rows: np.ndarray) -> np.ndarray:
    if len(rows) == 0:
        return rows
    return rows[np.lexsort(rows.T[::-1])]


def serve_server(graph=GRAPH, **kw):
    kw.setdefault("mem_words", 1 << 15)
    kw.setdefault("use_pallas_kernels", False)
    src, dst = graph
    return Server.from_graph(src, dst, **kw)


def labeled_sum(reg, name, label):
    """Sum of every series of ``name`` carrying ``label`` (any value)."""
    return sum(v for key, v in reg.series(name).items()
               if any(k == label for k, _ in key))


def unlabeled_value(reg, name, label):
    """The one series of ``name`` with no ``label`` label (the global)."""
    vals = [v for key, v in reg.series(name).items()
            if not any(k == label for k, _ in key)]
    assert len(vals) == 1, (name, vals)
    return vals[0]


# ---------------------------------------------------------------------------
# tracer unit behaviour
# ---------------------------------------------------------------------------

class TestTracer:
    def test_nesting_records_parent_chain(self):
        tr = Tracer()
        with tr.span("outer", n=1):
            with tr.span("inner"):
                tr.event("leaf", k=3)
        ev = tr.snapshot()
        begins = {e["name"]: e for e in ev if e["ph"] == "B"}
        assert begins["outer"]["parent"] is None
        assert begins["inner"]["parent"] == begins["outer"]["sid"]
        leaf = next(e for e in ev if e["ph"] == "i")
        assert leaf["parent"] == begins["inner"]["sid"]
        assert begins["outer"]["args"] == {"n": 1}
        # two ends, popping innermost first
        ends = [e for e in ev if e["ph"] == "E"]
        assert [e["sid"] for e in ends] == [begins["inner"]["sid"],
                                            begins["outer"]["sid"]]

    def test_span_names_in_order(self):
        tr = Tracer()
        with tr.span("a"):
            pass
        with tr.span("b"):
            with tr.span("a"):
                pass
        assert tr.span_names() == ["a", "b"]

    def test_ring_buffer_bounds_memory_and_counts_dropped(self):
        tr = Tracer(capacity=16)
        for i in range(50):
            tr.event("tick", i=i)
        assert len(tr.snapshot()) == 16
        assert tr.dropped == 34
        # the surviving window is the most recent one
        assert [e["args"]["i"] for e in tr.snapshot()] == list(range(34, 50))
        tr.clear()
        assert tr.snapshot() == [] and tr.dropped == 0

    def test_exception_unwinds_span_stack(self):
        tr = Tracer()
        with pytest.raises(RuntimeError):
            with tr.span("outer"):
                with tr.span("inner"):
                    raise RuntimeError("boom")
        with tr.span("after"):
            pass
        after = next(e for e in tr.snapshot()
                     if e["ph"] == "B" and e["name"] == "after")
        assert after["parent"] is None

    def test_threads_get_independent_stacks(self):
        tr = Tracer()
        seen = {}

        def worker():
            with tr.span("child"):
                seen["parent"] = next(
                    e["parent"] for e in reversed(tr.snapshot())
                    if e["ph"] == "B" and e["name"] == "child")

        with tr.span("main-span"):
            t = threading.Thread(target=worker)
            t.start()
            t.join()
        # the other thread's span must NOT parent under this thread's
        assert seen["parent"] is None

    def test_to_chrome_is_valid_and_balanced(self, tmp_path):
        tr = Tracer()
        with tr.lane("shard0"), tr.span("fabric.shard", shard=0):
            tr.event("cache.hit", words=8)
        with tr.span("engine.count"):
            pass
        doc = tr.to_chrome()
        json.loads(json.dumps(doc))       # round-trips
        ev = doc["traceEvents"]
        assert sum(1 for e in ev if e["ph"] == "B") \
            == sum(1 for e in ev if e["ph"] == "E")
        for e in ev:
            assert {"ph", "pid", "tid", "name"} <= set(e)
        lanes = {e["args"]["name"] for e in ev if e["ph"] == "M"}
        assert lanes == {"main", "shard0"}
        # lane events live in their own pid row
        pid_of = {e["args"]["name"]: e["pid"] for e in ev if e["ph"] == "M"}
        shard_b = next(e for e in ev
                       if e["ph"] == "B" and e["name"] == "fabric.shard")
        assert shard_b["pid"] == pid_of["shard0"]
        path = tr.export_chrome(str(tmp_path / "trace.json"))
        with open(path) as f:
            assert json.load(f)["traceEvents"]

    def test_to_chrome_drops_orphaned_ends(self):
        tr = Tracer(capacity=16)
        with tr.span("long"):
            for i in range(40):          # evicts the "long" begin
                tr.event("tick", i=i)
        ev = tr.to_chrome()["traceEvents"]
        assert sum(1 for e in ev if e["ph"] == "B") \
            == sum(1 for e in ev if e["ph"] == "E")

    def test_args_degrade_to_jsonable(self):
        tr = Tracer()
        tr.event("k", arr=np.int32(7), obj=object(), s="x", none=None)
        ev = tr.to_chrome()["traceEvents"]
        rec = next(e for e in ev if e["ph"] == "i")
        json.dumps(rec)
        assert rec["args"]["arr"] == 7
        assert isinstance(rec["args"]["obj"], str)

    def test_wrap_stage_is_identity_when_off(self):
        def fn(x):
            return x + 1
        assert wrap_stage(None, "box.fetch", fn) is fn
        tr = Tracer()
        wrapped = wrap_stage(tr, "box.fetch", fn)
        assert wrapped(1) == 2
        assert tr.span_names() == ["box.fetch"]


# ---------------------------------------------------------------------------
# metrics registry unit behaviour
# ---------------------------------------------------------------------------

class TestMetricsRegistry:
    def test_counters_gauges_histograms(self):
        reg = MetricsRegistry()
        reg.inc("kernel.invocations", 2, op="staged")
        reg.inc("kernel.invocations", 3, op="staged")
        reg.inc("kernel.invocations", 5, op="fused")
        reg.set("box.pool", 4, lane="all")
        for v in (1.0, 2.0, 10.0):
            reg.observe("serve.latency_s", v, mode="count")
        assert reg.get("kernel.invocations", op="staged") == 5
        assert reg.get("box.pool", lane="all") == 4
        assert reg.get("missing") is None
        assert sum(reg.series("kernel.invocations").values()) == 10
        assert reg.quantile("serve.latency_s", 0.5, mode="count") == 2.0
        assert reg.quantile("serve.latency_s", 1.0, mode="count") == 10.0
        assert reg.quantile("serve.latency_s", 0.5, mode="list") is None

    def test_snapshot_and_prom_text(self):
        reg = MetricsRegistry()
        reg.inc("io.block_reads", 7, tag="q0")
        reg.set("engine.n_boxes", 3.0)
        reg.observe("serve.latency_s", 0.25, mode="count")
        snap = reg.snapshot()
        assert snap["counters"]["io.block_reads"]['{tag="q0"}'] == 7
        assert snap["gauges"]["engine.n_boxes"][""] == 3.0
        h = snap["histograms"]["serve.latency_s"]['{mode="count"}']
        assert h["count"] == 1 and h["sum"] == 0.25
        text = reg.to_prom_text()
        assert '# TYPE io_block_reads counter' in text
        assert 'io_block_reads{tag="q0"} 7' in text
        assert 'serve_latency_s_count{mode="count"} 1' in text
        assert 'quantile="0.50"' in text

    def test_publish_stats_only_numeric_fields(self):
        reg = MetricsRegistry()
        stats = EngineStats()
        stats.n_boxes = 9
        reg.publish_stats(stats, "engine", mode="count")
        assert reg.get("engine.n_boxes", mode="count") == 9.0
        # non-numeric dataclass fields (lists, strings, None) are skipped
        for key in reg.series("engine.backend"):
            raise AssertionError(f"non-numeric field published: {key}")

    def test_default_registry_opt_in(self):
        assert default_registry() is None
        reg = MetricsRegistry()
        set_default_registry(reg)
        try:
            assert default_registry() is reg
        finally:
            set_default_registry(None)
        assert default_registry() is None


# ---------------------------------------------------------------------------
# queue-telemetry folding + the worker_utilization guard
# ---------------------------------------------------------------------------

def _tele(**kw):
    tele = dict(wait=0.0, build=0.0, compute=0.0, wall=0.0, pool=1,
                hi_boxes=0, hi_words=0)
    tele.update(kw)
    return tele


class TestQueueTelemetry:
    def test_zero_wall_reports_none(self):
        stats = EngineStats()
        merge_queue_telemetry(stats, _tele(pool=4), threading.Lock(), 2)
        assert stats.worker_utilization is None

    def test_zero_pool_reports_none(self):
        stats = EngineStats()
        merge_queue_telemetry(stats, _tele(wall=1.0, pool=0),
                              threading.Lock(), 2)
        assert stats.worker_utilization is None

    def test_regular_ratio(self):
        stats = EngineStats()
        merge_queue_telemetry(stats, _tele(build=1.0, compute=1.0,
                                           wall=1.0, pool=4),
                              threading.Lock(), 2)
        assert stats.worker_utilization == pytest.approx(0.5)

    def test_folds_into_registry(self):
        stats = EngineStats()
        reg = MetricsRegistry()
        merge_queue_telemetry(stats, _tele(build=0.5, wall=1.0, pool=3),
                              threading.Lock(), 2, metrics=reg,
                              lane="shard1")
        assert reg.get("box.pool", lane="shard1") == 3
        assert reg.get("box.build_s", lane="shard1") == pytest.approx(0.5)

    def test_folds_into_default_registry(self):
        stats = EngineStats()
        reg = MetricsRegistry()
        set_default_registry(reg)
        try:
            merge_queue_telemetry(stats, _tele(wall=1.0), threading.Lock(), 2)
        finally:
            set_default_registry(None)
        assert reg.get("box.pool", lane="all") == 1


# ---------------------------------------------------------------------------
# traced-off identity: tracing must never change execution
# ---------------------------------------------------------------------------

class TestTracedIdentity:
    def test_triangle_engine_byte_identical(self):
        src, dst = SMALL
        base = TriangleEngine(src, dst, mem_words=4096)
        want = base.count()
        want_reads = base.stats.block_reads

        tr = Tracer()
        reg = MetricsRegistry()
        eng = TriangleEngine(src, dst, mem_words=4096, tracer=tr,
                             metrics=reg)
        assert eng.count() == want
        assert eng.stats.block_reads == want_reads
        names = tr.span_names()
        assert "engine.count" in names
        assert "box.fetch" in names and "box.compute" in names
        assert reg.get("engine.n_boxes", mode="count") == eng.stats.n_boxes

    def test_triangle_engine_list_identical(self):
        src, dst = SMALL
        want = canon(TriangleEngine(src, dst, mem_words=4096).list())
        tr = Tracer()
        eng = TriangleEngine(src, dst, mem_words=4096, tracer=tr)
        np.testing.assert_array_equal(canon(eng.list()), want)
        assert "engine.list" in tr.span_names()

    def test_query_engine_byte_identical_and_kernel_events(self):
        src, dst = SMALL
        q = PATTERNS["triangle"]()
        base = QueryEngine.from_graph(q, src, dst, mem_words=1 << 14,
                                      backend="pallas")
        want = base.count()
        want_reads = base.stats.block_reads

        tr = Tracer()
        reg = MetricsRegistry()
        eng = QueryEngine.from_graph(q, src, dst, mem_words=1 << 14,
                                     backend="pallas", tracer=tr,
                                     metrics=reg)
        assert eng.count() == want
        assert eng.stats.block_reads == want_reads
        names = tr.span_names()
        assert "query.plan" in names and "query.boxes" in names
        launches = [e for e in tr.snapshot()
                    if e["ph"] == "i" and e["name"] == "kernel.launch"]
        assert launches, "pallas-lane run recorded no kernel launches"
        assert sum(reg.series("kernel.invocations").values()) > 0


# ---------------------------------------------------------------------------
# served runs: span taxonomy + registry/ledger exact-sum invariants
# ---------------------------------------------------------------------------

class TestServeObservability:
    def test_span_taxonomy_and_latency_histogram(self):
        """One served run produces the full acceptance taxonomy:
        admission, planning, per-box fetch/compute, a cache event, and a
        kernel launch (pallas lane, interpret on CPU)."""
        tr = Tracer()
        reg = MetricsRegistry()
        with serve_server(graph=SMALL, backend="pallas", tracer=tr,
                          metrics=reg) as srv:
            h = srv.submit("triangle", "count")
            got = h.result(timeout=300)
        src, dst = SMALL
        want = QueryEngine.from_graph(PATTERNS["triangle"](), src, dst,
                                      mem_words=1 << 14).count()
        assert got == want
        names = tr.span_names()
        for required in ("serve.admission", "serve.query", "query.plan",
                         "box.fetch", "box.compute"):
            assert required in names, (required, names)
        events = {e["name"] for e in tr.snapshot() if e["ph"] == "i"}
        assert any(n.startswith("cache.") for n in events), events
        assert "kernel.launch" in events, events
        assert reg.quantile("serve.latency_s", 0.5, mode="count",
                            status="done") is not None

    @settings(max_examples=5, deadline=None)
    @given(st.lists(st.sampled_from(["triangle", "path3", "four_clique"]),
                    min_size=1, max_size=3),
           st.sampled_from(["count", "list"]))
    def test_registry_sums_match_raw_ledgers(self, names, mode):
        reg = MetricsRegistry()
        with serve_server(graph=SMALL, metrics=reg) as srv:
            handles = [srv.submit(n, mode) for n in names]
            for h in handles:
                h.result(timeout=300)
            reg.collect()

            # io.*: per-tag series (including the _untagged residual)
            # sum to the raw BlockDevice globals, field by field
            for f in IO_FIELDS:
                raw = int(getattr(srv.device.stats, f))
                assert unlabeled_value(reg, f"io.{f}", "tag") == raw
                assert labeled_sum(reg, f"io.{f}", "tag") == raw, f
            # every tag partition got its own series plus the residual
            tags = {dict(k).get("tag")
                    for k in reg.series("io.block_reads") if k}
            assert "_untagged" in tags

            # cache.*: per-tenant series (including _shared) sum to the
            # raw SharedSliceCache globals
            for rel, cache in srv.caches.items():
                for f in CACHE_FIELDS:
                    raw = int(getattr(cache, f))
                    series = {k: v for k, v in
                              reg.series(f"cache.{f}").items()
                              if dict(k).get("relation") == rel}
                    tenant_sum = sum(
                        v for k, v in series.items()
                        if any(lk == "tenant" for lk, _ in k))
                    assert tenant_sum == raw, (rel, f)
                tenants = {dict(k).get("tenant")
                           for k in reg.series("cache.hits")
                           if dict(k).get("relation") == rel}
                assert "_shared" in tenants

    def test_departed_tenants_keep_summing(self):
        """Queries that finished (tenant unregistered) must stay in the
        per-tenant sum — `_gone` ledgers are part of the invariant."""
        reg = MetricsRegistry()
        with serve_server(graph=SMALL, metrics=reg) as srv:
            for _ in range(2):
                srv.submit("triangle", "count").result(timeout=300)
            reg.collect()
            cache = srv.caches["E"]
            hits = int(cache.hits)
            assert labeled_sum(reg, "cache.hits", "tenant") == hits


# ---------------------------------------------------------------------------
# spans inside the lanes, the listing tail and the sharded count path
# ---------------------------------------------------------------------------

def store_engine(path, tracer=None, **kw):
    """A store-backed engine over ``GRAPH`` whose plan has several boxes,
    every one on the binary lane."""
    src, dst = GRAPH
    kw.setdefault("backend", "binary")
    return TriangleEngine.ingest(path, (src, dst), orientation="degree",
                                 chunk_rows=256, mem_words=4096,
                                 tracer=tracer, **kw)


def parents(tracer):
    """[(span name, its parent span's name)] of every span, in order."""
    snap = tracer.snapshot()
    names = {e["sid"]: e["name"] for e in snap if e["ph"] == "B"}
    return [(e["name"], names.get(e["parent"])) for e in snap
            if e["ph"] == "B"]


def assert_nested(pairs, child, parent):
    got = {p for n, p in pairs if n == child}
    assert got == {parent}, (child, got)


class TestLaneSpans:
    @pytest.fixture(scope="class")
    def runs(self, tmp_path_factory):
        """An untraced and a traced engine over the same store, each run
        through a count and a listing."""
        d = tmp_path_factory.mktemp("lanes")
        out = {}
        for name, tr in (("plain", None), ("traced", Tracer())):
            eng = store_engine(d / f"{name}.store", tr)
            count = eng.count()
            count_stats = eng.stats
            rows = eng.list()
            out[name] = dict(tracer=tr, count=count, count_stats=count_stats,
                             rows=rows, list_stats=eng.stats)
        return out

    def test_count_opens_lane_spans_inside_each_box(self, runs):
        tr = runs["traced"]["tracer"]
        pairs = parents(tr)
        stats = runs["traced"]["count_stats"]
        assert stats.n_boxes > 1 and stats.n_binary_boxes > 1
        for lane in ("lane.pad", "lane.put", "lane.run"):
            assert_nested(pairs, lane, "box.compute")
            count_lanes = [n for n, p in pairs[:pairs.index(
                ("engine.list", None))] if n == lane]
            assert len(count_lanes) == stats.n_binary_boxes, lane
        for stage in ("box.compute", "box.wait"):
            assert {p for n, p in pairs if n == stage} \
                == {"engine.count", "engine.list"}, stage
        assert ("engine.plan", "engine.count") in pairs

    def test_lane_spans_follow_each_other_in_a_box(self, runs):
        snap = runs["traced"]["tracer"].snapshot()
        order = [e["name"] for e in snap if e["ph"] == "B"
                 and e["name"].startswith("lane.")]
        assert order[:3] == ["lane.pad", "lane.put", "lane.run"]
        assert order[-4:] == ["lane.pad", "lane.put", "lane.run",
                              "lane.readout"]

    def test_list_opens_readout_concat_and_sort(self, runs):
        pairs = parents(runs["traced"]["tracer"])
        assert_nested(pairs, "lane.readout", "box.compute")
        assert_nested(pairs, "list.concat", "engine.list")
        assert_nested(pairs, "engine.canonical", "engine.list")
        assert ("box.wait", "engine.list") in pairs
        assert ("engine.plan", "engine.list") in pairs
        readouts = sum(n == "lane.readout" for n, _ in pairs)
        assert readouts == runs["traced"]["list_stats"].n_streamed_boxes

    def test_fetch_spans_stay_on_the_prefetch_thread(self, runs):
        """The prefetch thread's spans are unchanged: ``box.fetch`` opens
        at top level of its own thread, never inside the caller's spans."""
        pairs = parents(runs["traced"]["tracer"])
        assert_nested(pairs, "box.fetch", None)

    def test_traced_and_untraced_runs_are_identical(self, runs):
        plain, traced = runs["plain"], runs["traced"]
        assert traced["count"] == plain["count"]
        np.testing.assert_array_equal(traced["rows"], plain["rows"])
        for key in ("count_stats", "list_stats"):
            assert traced[key].block_reads == plain[key].block_reads
            assert traced[key].device_transfer_bytes \
                == plain[key].device_transfer_bytes

    def test_count_binary_fills_device_transfer_bytes(self, runs):
        """The binary lane notes one launch per box on the kernel ledger,
        its padded matrix among the bytes shipped."""
        stats = runs["plain"]["count_stats"]
        assert stats.device_invocations == stats.n_binary_boxes
        assert stats.max_box_device_invocations == 1
        assert stats.device_transfer_bytes > 4 * stats.padded_words

    def test_count_binary_bytes_per_box(self):
        from repro.core.executor import StreamingExecutor, _pow2
        from repro.data.edgestore import InMemoryEdgeSource
        from repro.kernels import ledger

        src, dst = SMALL
        eng = TriangleEngine(src, dst, mem_words=4096)
        stats = EngineStats()
        ex = StreamingExecutor(InMemoryEdgeSource(eng.indptr, eng.indices),
                               pick_backend=lambda *a: "binary",
                               chunk=512, stats=stats)
        want = 0
        for box in eng.plan():
            slc = ex._materialize(box)
            if slc is None or slc.n_edges == 0:
                continue
            with ledger.attach() as kl:
                ex._count_slice(slc)
            edges = _pow2(slc.n_edges, lo=256)
            assert kl.invocations == 1
            assert kl.bytes_in == slc.npad.nbytes + 2 * 4 * edges
            assert kl.bytes_out == 4
            want += kl.transfer_bytes
        assert want > 0 and stats.device_transfer_bytes == want


SHARDED_SPANS = r"""
import json, tempfile
from pathlib import Path
import jax
from repro.core.engine import TriangleEngine
from repro.data.graphs import rmat_graph
from repro.obs import Tracer

assert len(jax.devices()) == 4
src, dst = rmat_graph(512, 6000, seed=21)
d = Path(tempfile.mkdtemp())
out = {}
for name, tr in (("plain", None), ("traced", Tracer())):
    eng = TriangleEngine.ingest(d / f"{name}.store", (src, dst),
                                orientation="degree", chunk_rows=256,
                                mem_words=4096, backend="binary",
                                shard=True, tracer=tr)
    count = eng.count()
    reads = eng.stats.block_reads
    edges = eng.stats.shard_edges
    rows = eng.list()
    res = {"count": count, "reads": reads, "shard_edges": edges,
           "list_reads": eng.stats.block_reads,
           "rows": rows.tolist()}
    if tr is not None:
        snap = tr.snapshot()
        names = {e["sid"]: e["name"] for e in snap if e["ph"] == "B"}
        res["pairs"] = [[e["name"], names.get(e["parent"])]
                        for e in snap if e["ph"] == "B"]
    out[name] = res
print(json.dumps(out))
"""


def test_sharded_count_spans_on_four_devices():
    """On four virtual CPU devices a traced sharded ``count()`` opens the
    five ``shard.*`` spans inside ``engine.count``, in order, and gives
    the untraced run's count, listing and block reads."""
    import os
    import subprocess
    import sys
    from pathlib import Path

    root = Path(__file__).resolve().parents[1]
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=os.environ.get("XLA_FLAGS", "")
               + " --xla_force_host_platform_device_count=4",
               PYTHONPATH=str(root / "src") + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    p = subprocess.run([sys.executable, "-c", SHARDED_SPANS], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    plain, traced = out["plain"], out["traced"]
    for key in ("count", "reads", "shard_edges", "list_reads", "rows"):
        assert traced[key] == plain[key], key
    assert len(plain["shard_edges"]) == 4
    pairs = [tuple(x) for x in traced["pairs"]]
    stages = ["shard.stage", "shard.edges", "shard.slices", "shard.put",
              "shard.run"]
    in_count = pairs[:pairs.index(("engine.list", None))]
    assert [n for n, _ in in_count if n.startswith("shard.")] == stages
    for name in stages:
        assert (name, "engine.count") in in_count
    assert ("engine.plan", "engine.count") in in_count
    # the listing stages its store too, and sorts inside engine.list
    assert ("shard.stage", "engine.list") in pairs
    assert ("engine.canonical", "engine.list") in pairs
