"""Distributed box fabric (PR 9): every mesh/sharding path pinned to the
single-host ``QueryEngine`` oracle.

The fabric's contract is that distribution changes WHERE boxes run, never
what they compute or what I/O they are charged: per mesh shape x pattern,
the distributed count/listing must be byte-identical to the single-host
engine, the per-shard ``BlockDevice`` ledgers must be byte-identical to a
solo engine running the same restricted plan over the full data
(``Fabric.oracle_engine``), and each shard's measured block reads must sit
inside the Thm. 13 envelope at its local budget. The CI ``fabric`` job
runs this file under ``XLA_FLAGS=--xla_force_host_platform_device_count=48``
plus true multi-process subprocess workers.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from conftest import ThreadGuard  # noqa: F401  (thread_guard fixture home)
from repro.core.lftj_jax import csr_from_edges, orient_edges
from repro.data.edgestore import InMemoryEdgeSource, write_edge_store
from repro.data.graphs import random_graph, rmat_graph
from repro.launch.mesh import fabric_mesh, resolve_fabric_shards
from repro.parallel.fabric import (Fabric, FabricShippingError,
                                   ShippedEdgeSource)
from repro.query.executor import QueryEngine
from repro.query.patterns import PATTERNS
from repro.query.planner import thm13_io_bound

ENV_WORKERS = max(2, int(os.environ.get("REPRO_TEST_WORKERS", "4")))
SRC = os.path.join(os.path.dirname(__file__), "..", "src")

PATTERN_NAMES = ("triangle", "four_clique", "diamond", "path3")
MESH_SHAPES = (1, 2, 4, 8)

SMALL = random_graph(96, 400, seed=7)
GRAPH = rmat_graph(128, 600, seed=3)

_ORACLE = {}


def oracle(name, mode="count", graph=SMALL, mem_words=1 << 12):
    """Cached single-host QueryEngine result for the acceptance matrix."""
    key = (name, mode, id(graph), mem_words)
    if key not in _ORACLE:
        src, dst = graph
        eng = QueryEngine.from_graph(PATTERNS[name](), src, dst,
                                     mem_words=mem_words)
        _ORACLE[key] = eng.count() if mode == "count" else eng.list()
    return _ORACLE[key]


def small_fabric(name, shards, graph=SMALL, **kw):
    kw.setdefault("mem_words", 1 << 12)
    src, dst = graph
    return Fabric.from_graph(PATTERNS[name](), src, dst,
                             n_shards=shards, **kw)


def _sub_env(n_devices=None):
    env = dict(os.environ)
    if n_devices:
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                            + f" --xla_force_host_platform_device_count"
                              f"={n_devices}")
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.abspath(SRC) + os.pathsep \
        + env.get("PYTHONPATH", "")
    return env


@pytest.fixture(scope="module")
def store_path(tmp_path_factory):
    src, dst = GRAPH
    path = str(tmp_path_factory.mktemp("fabric") / "g.csr")
    write_edge_store(path, src, dst, orientation="minmax", chunk_rows=32)
    return path


# ---------------------------------------------------------------------------
# acceptance matrix: distributed results == single-host oracle
# ---------------------------------------------------------------------------

class TestFabricMatchesSingleHost:
    @pytest.mark.parametrize("shards", MESH_SHAPES)
    @pytest.mark.parametrize("pattern", PATTERN_NAMES)
    def test_count(self, pattern, shards):
        fab = small_fabric(pattern, shards)
        assert fab.count() == oracle(pattern)
        assert fab.stats.n_shards == shards
        # the schedule is an exact partition of the global box list
        lay = fab.layout()
        flat = sorted(b for ids in lay.schedule for b in ids)
        assert flat == list(range(len(lay.plan.boxes)))
        assert fab.stats.sum_block_reads == \
            sum(fab.stats.shard_block_reads)

    @pytest.mark.parametrize("shards", (1, 4, 8))
    @pytest.mark.parametrize("pattern", PATTERN_NAMES)
    def test_listing(self, pattern, shards):
        fab = small_fabric(pattern, shards)
        np.testing.assert_array_equal(fab.list(), oracle(pattern, "list"))

    @pytest.mark.parametrize("pattern", PATTERN_NAMES)
    def test_plan_identical_to_single_host(self, pattern):
        """The fabric plans on an ordinary full-source engine: its global
        plan is the single-host plan, and each shard's sub-plan is exactly
        the scheduled subset of it."""
        fab = small_fabric(pattern, 4)
        src, dst = SMALL
        solo = QueryEngine.from_graph(PATTERNS[pattern](), src, dst,
                                      mem_words=1 << 12)
        gp, sp = fab.layout().plan, solo.plan()
        assert gp.order == sp.order
        assert gp.rank == sp.rank
        assert gp.boxes == sp.boxes
        assert gp.lanes == sp.lanes
        for s in range(4):
            eng = fab.shard_engine(s)
            assert eng.plan().boxes == \
                [gp.boxes[i] for i in fab.layout().schedule[s]]

    def test_reduce_arg_validated(self):
        fab = small_fabric("triangle", 2)
        with pytest.raises(ValueError, match="reduce"):
            fab.count(reduce="bogus")


# ---------------------------------------------------------------------------
# per-shard ledger byte-identity vs the solo oracle engine
# ---------------------------------------------------------------------------

LEDGER_FIELDS = ("block_reads", "block_writes", "word_reads", "cache_hits",
                 "cache_misses", "cache_hit_words", "slice_words_read",
                 "n_results")

CONFIGS = {
    "mem": dict(store=False, cache_words=0, workers=1, skew="uniform"),
    "store": dict(store=True, cache_words=0, workers=1, skew="uniform"),
    "store_cache": dict(store=True, cache_words=1 << 10, workers=1,
                        skew="uniform"),
    "store_workers": dict(store=True, cache_words=0, workers=ENV_WORKERS,
                          skew="uniform"),
    "store_skew": dict(store=True, cache_words=0, workers=1,
                       skew="heavy_light"),
}


class TestShardLedgerByteIdentity:
    @pytest.mark.parametrize("cfg", list(CONFIGS))
    @pytest.mark.parametrize("pattern", ["triangle", "diamond"])
    def test_shard_equals_solo_oracle(self, pattern, cfg, store_path):
        """A shard over SHIPPED byte ranges and a solo engine over the
        FULL data, both restricted to the shard's boxes on fresh
        identically-configured devices, land on byte-identical ledgers —
        under stores, caches, multi-worker drains and skewed plans."""
        c = CONFIGS[cfg]
        kw = dict(mem_words=1 << 11, cache_words=c["cache_words"],
                  io_block_words=64, workers=c["workers"], skew=c["skew"])
        mode = "list" if cfg == "store" else "count"
        for shards in (2, 4):
            if c["store"]:
                fab = Fabric(PATTERNS[pattern](), store=store_path,
                             n_shards=shards, **kw)
            else:
                src, dst = GRAPH
                fab = Fabric.from_graph(PATTERNS[pattern](), src, dst,
                                        n_shards=shards, **kw)
            for s in range(shards):
                rep = fab.run_local(s, mode)
                orc = fab.oracle_engine(s)
                want = orc.run_boxes(mode)
                assert len(rep.results) == len(want)
                for got_r, want_r in zip(rep.results, want):
                    if want_r is None:
                        assert got_r is None
                    elif mode == "count":
                        assert int(got_r) == int(want_r)
                    else:
                        np.testing.assert_array_equal(got_r, want_r)
                for f in LEDGER_FIELDS:
                    assert getattr(rep.stats, f) == \
                        getattr(orc.stats, f), (cfg, pattern, shards, s, f)
                assert rep.io.block_reads == orc.device.stats.block_reads
                assert rep.io.word_reads == orc.device.stats.word_reads

    def test_summed_shard_reads_equal_solo_sum(self):
        """The fabric's aggregate block reads are exactly the sum of the
        per-shard solo envelopes — distribution adds no hidden I/O."""
        src, dst = GRAPH
        fab = Fabric.from_graph(PATTERNS["triangle"](), src, dst,
                                n_shards=4, mem_words=1 << 11,
                                io_block_words=64)
        fab.count()
        solo = 0
        for s in range(4):
            orc = fab.oracle_engine(s)
            orc.run_boxes("count")
            solo += orc.stats.block_reads
        assert fab.stats.sum_block_reads == solo


# ---------------------------------------------------------------------------
# Thm. 13 envelope at each shard's local budget
# ---------------------------------------------------------------------------

class TestThm13Envelope:
    @pytest.mark.parametrize("pattern", ["triangle", "diamond"])
    def test_per_shard_io_within_envelope(self, pattern):
        m, b = 1 << 11, 64
        src, dst = GRAPH
        fab = Fabric.from_graph(PATTERNS[pattern](), src, dst, n_shards=4,
                                mem_words=m, io_block_words=b)
        fab.count()
        rank = fab.layout().plan.rank
        for rep in fab.reports:
            if not rep.box_ids:
                continue
            inp = max(1, rep.shipped_words)
            # rank-r no-spill term + one scan of the shipped input
            bound = thm13_io_bound(inp, m, b, rank) + inp / b
            assert rep.stats.block_reads <= 12 * bound \
                + 8 * len(rep.box_ids) + 16, \
                (pattern, rep.shard, rep.stats.block_reads, bound)


# ---------------------------------------------------------------------------
# shipping safety: under-shipping is loud, never wrong
# ---------------------------------------------------------------------------

class TestShipping:
    def _base(self):
        src, dst = random_graph(64, 200, seed=1)
        a, b = orient_edges(src, dst)
        nv = int(max(a.max(initial=-1), b.max(initial=-1))) + 1
        ip, ix = csr_from_edges(a, b, n_nodes=nv)
        return InMemoryEdgeSource(ip, ix)

    def test_shipped_reads_match_base(self):
        base = self._base()
        s = ShippedEdgeSource(base, [(0, 9)])
        ip_got, vals_got = s.read_rows(0, 9)
        ip_want, vals_want = base.read_rows(0, 9)
        np.testing.assert_array_equal(ip_got, ip_want)
        np.testing.assert_array_equal(vals_got, vals_want)
        assert s.shipped_words == len(vals_want)

    def test_read_outside_shipped_ranges_raises(self):
        base = self._base()
        s = ShippedEdgeSource(base, [(0, 5)])
        with pytest.raises(FabricShippingError):
            s.read_rows(3, 10)

    def test_gap_between_shipped_ranges_raises(self):
        base = self._base()
        s = ShippedEdgeSource(base, [(0, 3), (8, 9)])
        with pytest.raises(FabricShippingError):
            s.read_rows(2, 9)
        # both covered ends still serve
        np.testing.assert_array_equal(s.read_rows(8, 9)[1],
                                      base.read_rows(8, 9)[1])


# ---------------------------------------------------------------------------
# hypothesis stress: patterns x mesh shapes x workers x cache x skew
# ---------------------------------------------------------------------------

class TestFabricStress:
    @settings(max_examples=8, deadline=None)
    @given(st.integers(0, 10_000),
           st.sampled_from(list(PATTERN_NAMES)),
           st.sampled_from(list(MESH_SHAPES)),
           st.sampled_from([1, ENV_WORKERS]),
           st.sampled_from([0, 1 << 10]),
           st.sampled_from(["uniform", "heavy_light"]))
    def test_fabric_equals_single_host(self, seed, pattern, shards,
                                       workers, cache_words, skew):
        src, dst = random_graph(64, 240, seed=seed % 997)
        kw = dict(mem_words=1 << 11, cache_words=cache_words,
                  workers=workers, skew=skew)
        fab = Fabric.from_graph(PATTERNS[pattern](), src, dst,
                                n_shards=shards, **kw)
        solo = QueryEngine.from_graph(PATTERNS[pattern](), src, dst, **kw)
        assert fab.count() == solo.count()
        if seed % 2:
            np.testing.assert_array_equal(fab.list(), solo.list())


# ---------------------------------------------------------------------------
# mesh reduction (in-process + 48 fake devices in a subprocess)
# ---------------------------------------------------------------------------

class TestMeshReduce:
    def test_mesh_psum_equals_host_sum(self):
        import jax
        mesh = fabric_mesh(len(jax.devices()))
        fab = small_fabric("triangle", None, mesh=mesh)
        assert fab.n_shards == int(mesh.devices.size)
        assert fab.count(reduce="mesh") == oracle("triangle")
        # auto picks the mesh when one is attached
        assert fab.count() == oracle("triangle")

    def test_mesh_psum_is_exact_past_int32(self):
        """Partials beyond 2^31 (a scale-22 Graph500 graph has ~2e9
        triangles) reduce exactly, not wrapped through int32 lanes."""
        import jax
        n = len(jax.devices())
        fab = small_fabric("triangle", None, mesh=fabric_mesh(n))
        partials = [3_000_000_007 + i for i in range(n)]
        assert fab._mesh_sum(partials) == sum(partials)

    def test_mesh_reduce_rejects_partial_process(self):
        fab = small_fabric("triangle", 2, process_index=0, n_processes=2)
        with pytest.raises(ValueError, match="n_processes"):
            fab.count(reduce="mesh")

    def test_48_fake_devices_subprocess(self):
        """Acceptance: a 48-device CPU mesh (XLA forced host devices)
        reproduces the single-host count through the shard_map psum
        reduction at mesh shapes 8 and 48, and the 48-shard listing is
        byte-identical."""
        script = r"""
import numpy as np, jax
assert len(jax.devices()) == 48, jax.devices()
from repro.data.graphs import random_graph
from repro.launch.mesh import fabric_mesh, resolve_fabric_shards
from repro.parallel.fabric import Fabric
from repro.query.executor import QueryEngine
from repro.query.patterns import PATTERNS

assert resolve_fabric_shards() == 48
src, dst = random_graph(96, 400, seed=7)
want = QueryEngine.from_graph(PATTERNS["triangle"](), src, dst,
                              mem_words=1 << 12).count()
for shards in (8, 48):
    fab = Fabric.from_graph(PATTERNS["triangle"](), src, dst,
                            n_shards=shards, mem_words=1 << 12,
                            mesh=fabric_mesh(shards))
    got = fab.count(reduce="mesh")
    assert got == want, (shards, got, want)
    assert fab.stats.n_shards == shards
rows = Fabric.from_graph(PATTERNS["path3"](), src, dst, n_shards=48,
                         mem_words=1 << 12).list()
ref = QueryEngine.from_graph(PATTERNS["path3"](), src, dst,
                             mem_words=1 << 12).list()
assert np.array_equal(rows, ref)
print("FABRIC-MESH48-OK")
"""
        res = subprocess.run([sys.executable, "-c", script],
                             capture_output=True, text=True,
                             env=_sub_env(48), timeout=600)
        assert res.returncode == 0, res.stderr[-2000:]
        assert "FABRIC-MESH48-OK" in res.stdout


# ---------------------------------------------------------------------------
# multi-process protocol: worker CLI + partial merging
# ---------------------------------------------------------------------------

class TestMultiProcess:
    def test_worker_cli_two_processes_merge(self, tmp_path):
        """True multi-process run: two worker processes each execute their
        ``shard % 2 == process_index`` slice of a 5-shard fabric and emit
        JSON partials; the merged count equals the single-host oracle."""
        parts = []
        for p in range(2):
            out = tmp_path / f"part{p}.json"
            res = subprocess.run(
                [sys.executable, "-m", "repro.parallel.fabric",
                 "--pattern", "triangle", "--nv", "96", "--ne", "400",
                 "--seed", "7", "--shards", "5", "--mem-words", "4096",
                 "--process-index", str(p), "--n-processes", "2",
                 "--out", str(out)],
                capture_output=True, text=True, env=_sub_env(),
                timeout=600)
            assert res.returncode == 0, res.stderr[-2000:]
            assert "FABRIC-PARTIAL-OK" in res.stdout
            parts.append(json.loads(out.read_text()))
        assert Fabric.merge_partials(parts) == oracle("triangle")
        with pytest.raises(ValueError, match="missing shard"):
            Fabric.merge_partials(parts[:1])

    def test_partial_merge_list_mode(self):
        """partial()/merge_partials round-trips listings through JSON and
        lands byte-identical to the single-host listing."""
        fabs = [small_fabric("diamond", 4, process_index=p, n_processes=2)
                for p in range(2)]
        parts = [json.loads(json.dumps(f.partial("list"))) for f in fabs]
        merged = Fabric.merge_partials(parts)
        np.testing.assert_array_equal(merged, oracle("diamond", "list"))

    def test_process_index_validated(self):
        with pytest.raises(ValueError, match="process_index"):
            small_fabric("triangle", 4, process_index=3, n_processes=2)


# ---------------------------------------------------------------------------
# serving layer integration (admission-gated fabric runs)
# ---------------------------------------------------------------------------

class TestServeFabric:
    def test_fabric_run_matches_served_query(self, thread_guard):
        from repro.serve import Server, Session

        src, dst = SMALL
        srv = Server.from_graph(src, dst, mem_words=1 << 15,
                                use_pallas_kernels=False)
        try:
            want = srv.submit("triangle").result(timeout=300)
            got, stats = srv.fabric_run("triangle", "count", n_shards=4)
            assert got == want == oracle("triangle")
            assert stats.n_shards == 4
            rows, _ = srv.fabric_run("triangle", "list", n_shards=4)
            np.testing.assert_array_equal(rows, oracle("triangle", "list"))
            # the reservation is fully returned afterwards
            assert srv.admission.reserved_words == 0
            assert srv.admission.active == 0
            with Session(srv) as ses:
                assert ses.fabric_count("triangle", n_shards=2) == want
        finally:
            srv.close()
