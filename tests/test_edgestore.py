"""Out-of-core path: edge store format, streaming engine equivalence,
bounded working set, prefetcher semantics.

Headline acceptance (ISSUE 2): ``TriangleEngine`` produces identical counts
and listings whether fed in-memory arrays or a ``data/edgestore`` path, with
the streaming path's per-box working set bounded by the planner budget (plus
pinned spill rows) on a graph whose padded neighbor matrix exceeds the
budget — and the run reports measured block I/Os.
"""

import os

import numpy as np
import pytest

from repro.core import (BlockDevice, TriangleEngine, TrieArray,
                        lftj_triangle_count, orient_edges, pad_neighbors,
                        plan_boxes_from_degrees)
from repro.core.lftj_jax import csr_from_edges
from repro.data.edgestore import (EdgeStore, InMemoryEdgeSource,
                                  write_edge_store)
from repro.data.graphs import rmat_graph
from repro.data.pipeline import Prefetcher


def er_graph(n, p, seed):
    rng = np.random.default_rng(seed)
    adj = np.triu(rng.random((n, n)) < p, k=1)
    src, dst = np.nonzero(adj)
    return src.astype(np.int64), dst.astype(np.int64)


def grid_graph(n):
    i, j = np.meshgrid(np.arange(n), np.arange(n), indexing="ij")
    v = (i * n + j)
    right = np.stack([v[:, :-1].ravel(), v[:, 1:].ravel()], 1)
    down = np.stack([v[:-1, :].ravel(), v[1:, :].ravel()], 1)
    e = np.concatenate([right, down])
    return e[:, 0], e[:, 1]


def reference(src, dst):
    a, b = orient_edges(src, dst)
    out = []
    n = lftj_triangle_count(TrieArray.from_edges(a, b), emit=out.append)
    tris = np.asarray(out, dtype=np.int64).reshape(-1, 3)
    tris = np.sort(tris, axis=1)
    order = np.lexsort((tris[:, 2], tris[:, 1], tris[:, 0]))
    return n, tris[order]


# ---------------------------------------------------------------------------
# format: write -> mmap read roundtrip
# ---------------------------------------------------------------------------

class TestEdgeStoreFormat:
    def test_roundtrip_rows_match_csr(self, tmp_path):
        src, dst = rmat_graph(200, 2500, seed=4)
        a, b = orient_edges(src, dst)
        indptr, indices = csr_from_edges(a, b)
        path = write_edge_store(tmp_path / "g.csr", src, dst,
                                chunk_rows=7, align_words=16)
        store = EdgeStore(path)
        assert store.n_nodes == len(indptr) - 1
        assert store.n_edges == len(indices)
        assert store.orientation == "minmax"
        np.testing.assert_array_equal(store.indptr, indptr)
        # row ranges straddling chunk boundaries reassemble exactly
        for lo, hi in [(0, store.n_nodes - 1), (0, 6), (5, 9), (6, 7),
                       (13, 41), (store.n_nodes - 3, store.n_nodes - 1)]:
            ip, vals = store.read_rows(lo, hi)
            np.testing.assert_array_equal(
                vals, indices[indptr[lo]:indptr[hi + 1]])
            np.testing.assert_array_equal(
                ip, indptr[lo:hi + 2] - indptr[lo])

    def test_empty_graph_store_roundtrip(self, tmp_path):
        """Regression: an edgeless store has no indices region; opening it
        must not attempt a zero-length mmap past EOF."""
        path = write_edge_store(tmp_path / "empty.csr",
                                np.zeros(0, int), np.zeros(0, int))
        store = EdgeStore(path)
        assert store.n_edges == 0
        eng = TriangleEngine(store=path)
        assert eng.count() == 0
        assert eng.list().shape == (0, 3)

    def test_engine_requires_edges_or_store(self):
        with pytest.raises(ValueError, match="either"):
            TriangleEngine()

    def test_bad_magic_rejected(self, tmp_path):
        p = tmp_path / "junk.bin"
        p.write_bytes(b"\x00" * 256)
        with pytest.raises(ValueError, match="magic"):
            EdgeStore(p)

    def test_reads_are_charged_to_device(self, tmp_path):
        src, dst = rmat_graph(128, 1500, seed=1)
        path = write_edge_store(tmp_path / "g.csr", src, dst,
                                chunk_rows=16, align_words=8)
        dev = BlockDevice(block_words=8, cache_blocks=4)
        store = EdgeStore(path, device=dev)
        _, vals = store.read_rows(0, store.n_nodes - 1)
        assert dev.stats.word_reads == len(vals)
        # every word costs at most one block fetch; sequential reads amortize
        assert 1 <= dev.stats.block_reads <= len(vals) // 8 + store.n_chunks + 1

    def test_in_memory_source_matches_store(self, tmp_path):
        src, dst = er_graph(40, 0.2, seed=2)
        a, b = orient_edges(src, dst)
        indptr, indices = csr_from_edges(a, b)
        path = write_edge_store(tmp_path / "g.csr", src, dst, chunk_rows=4)
        store = EdgeStore(path)
        mem = InMemoryEdgeSource(indptr, indices)
        for lo, hi in [(0, 5), (3, 17), (0, store.n_nodes - 1)]:
            ip_s, v_s = store.read_rows(lo, hi)
            ip_m, v_m = mem.read_rows(lo, hi)
            np.testing.assert_array_equal(v_s, v_m)
            np.testing.assert_array_equal(ip_s, ip_m)


# ---------------------------------------------------------------------------
# planner: degree-index plan partitions the edge set
# ---------------------------------------------------------------------------

class TestDegreePlanner:
    def test_boxes_partition_oriented_edges(self):
        src, dst = rmat_graph(128, 2000, seed=3)
        a, b = orient_edges(src, dst)
        indptr, _ = csr_from_edges(a, b)
        boxes = plan_boxes_from_degrees(indptr, mem_words=300)
        assert len(boxes) > 1
        covered = np.zeros(len(a), dtype=int)
        for (lx, hx, ly, hy) in boxes:
            covered += ((a >= lx) & (a <= hx) & (b >= ly) & (b <= hy))
        assert (covered == 1).all()

    def test_single_box_when_budget_fits(self):
        src, dst = er_graph(20, 0.3, seed=0)
        a, b = orient_edges(src, dst)
        indptr, _ = csr_from_edges(a, b)
        boxes = plan_boxes_from_degrees(indptr, mem_words=1 << 20)
        assert boxes == [(0, len(indptr) - 2, 0, len(indptr) - 2)]

    def test_x_ranges_respect_budget_except_pinned(self):
        src, dst = rmat_graph(128, 2000, seed=3)
        a, b = orient_edges(src, dst)
        indptr, _ = csr_from_edges(a, b)
        mem = 300
        bx = int(mem * 4 / 5)
        deg = np.diff(indptr)
        cost = np.where(deg > 0, deg + 2, 0)
        for (lx, hx, _ly, _hy) in plan_boxes_from_degrees(indptr, mem):
            words = int(cost[lx:hx + 1].sum())
            assert words <= bx or lx == hx, (lx, hx, words)


# ---------------------------------------------------------------------------
# engine equivalence: in-memory vs edge-store-backed execution
# ---------------------------------------------------------------------------

GRAPHS = [
    ("er", er_graph(40, 0.18, seed=7)),
    ("rmat", rmat_graph(128, 1500, seed=7)),
    ("grid", grid_graph(6)),
]


class TestOutOfCoreEquivalence:
    @pytest.mark.parametrize("name,edges", GRAPHS, ids=[g[0] for g in GRAPHS])
    def test_count_and_list_match_memory_engine(self, tmp_path, name, edges):
        src, dst = edges
        want_n, want_tris = reference(src, dst)
        path = write_edge_store(tmp_path / f"{name}.csr", src, dst,
                                chunk_rows=16, align_words=8)
        for mem_words in (None, 150):
            eng_m = TriangleEngine(src, dst, mem_words=mem_words)
            eng_s = TriangleEngine(store=path, mem_words=mem_words,
                                   io_block_words=64)
            assert eng_m.count() == want_n
            assert eng_s.count() == want_n, (name, mem_words)
            if mem_words is not None and len(src) > 60:
                assert eng_s.stats.n_boxes > 1    # budget forces many boxes
            np.testing.assert_array_equal(eng_s.list(), want_tris)
            np.testing.assert_array_equal(eng_m.list(), want_tris)

    def test_store_backed_run_reports_block_io(self, tmp_path):
        src, dst = rmat_graph(128, 1500, seed=9)
        path = write_edge_store(tmp_path / "g.csr", src, dst,
                                chunk_rows=16, align_words=8)
        eng = TriangleEngine(store=path, mem_words=200, io_block_words=64)
        eng.count()
        assert eng.stats.source == "edgestore"
        assert eng.stats.block_reads > 0
        assert eng.stats.word_reads >= eng.stats.slice_words_read > 0
        n = eng.count()
        tris = TriangleEngine(store=path, mem_words=200).list()
        assert len(tris) == n

    def test_sharded_store_backed_agrees(self, tmp_path):
        src, dst = rmat_graph(128, 1500, seed=11)
        want_n, want_tris = reference(src, dst)
        path = write_edge_store(tmp_path / "g.csr", src, dst, chunk_rows=32)
        eng = TriangleEngine(store=path, mem_words=250, shard=True)
        assert eng.count() == want_n
        np.testing.assert_array_equal(eng.list(), want_tris)


class TestBoundedWorkingSet:
    def test_streaming_working_set_bounded_by_budget(self, tmp_path):
        """Acceptance: on a graph whose padded neighbor matrix exceeds the
        budget, the streaming path (a) never materializes the global npad,
        (b) DMAs at most budget + O(pinned row) words per box, and (c) the
        per-box padded slice stays far below the global matrix."""
        src, dst = rmat_graph(512, 6000, seed=5)
        a, b = orient_edges(src, dst)
        indptr, indices = csr_from_edges(a, b)
        npad_words = pad_neighbors(indptr, indices).size
        csr_words = len(indices) + 2 * (len(indptr) - 1)
        budget = max(256, csr_words // 8)
        assert npad_words > budget          # the premise of the test
        path = write_edge_store(tmp_path / "big.csr", src, dst,
                                chunk_rows=64, align_words=32)
        eng = TriangleEngine(store=path, mem_words=budget, io_block_words=64)
        want_n, want_tris = reference(src, dst)
        assert eng.count() == want_n
        assert eng.stats.n_boxes > 1
        # (a) global padded matrix never built, edge list never resident
        assert eng._npad is None and eng._npad_host is None
        assert eng.indices is None
        # (b) raw words DMA'd per box ≤ budget, unless a single pinned row
        # (the plan-level spill) exceeds it by itself
        max_row = int(np.diff(indptr).max()) + 2
        assert eng.stats.max_slice_words <= max(budget, 2 * max_row), \
            (eng.stats.max_slice_words, budget)
        # (c) compacted per-box padding stays well below the global matrix
        assert eng.stats.max_slice_padded_words < npad_words / 2
        np.testing.assert_array_equal(
            TriangleEngine(store=path, mem_words=budget).list(), want_tris)


# ---------------------------------------------------------------------------
# prefetcher: ordering, exception propagation, early close
# ---------------------------------------------------------------------------

class TestPrefetcher:
    def test_preserves_order(self):
        assert list(Prefetcher(iter(range(100)), depth=3)) == list(range(100))

    def test_propagates_producer_exception(self):
        def gen():
            yield 1
            yield 2
            raise RuntimeError("disk on fire")

        pf = Prefetcher(gen(), depth=1)
        assert next(pf) == 1
        assert next(pf) == 2
        with pytest.raises(RuntimeError, match="disk on fire"):
            next(pf)

    def test_exception_before_first_item(self):
        def gen():
            raise ValueError("bad header")
            yield 1  # pragma: no cover

        with pytest.raises(ValueError, match="bad header"):
            next(Prefetcher(gen(), depth=2))

    def test_close_stops_producer(self):
        produced = []

        def gen():
            for i in range(10_000):
                produced.append(i)
                yield i

        pf = Prefetcher(gen(), depth=2)
        assert next(pf) == 0
        pf.close()
        pf.thread.join(timeout=5)
        assert not pf.thread.is_alive()
        assert len(produced) < 10_000       # stopped early, not drained

    def test_close_is_idempotent_under_double_close(self):
        """Stress contract: close() joins the producer and a second (or
        third) close is a cheap no-op — no hang, no error, no thread."""
        pf = Prefetcher(iter(range(1000)), depth=2)
        assert next(pf) == 0
        pf.close()
        assert not pf.thread.is_alive()
        pf.close()                          # double-close: no-op
        pf.close()
        assert not pf.thread.is_alive()

    def test_close_after_exhaustion(self):
        pf = Prefetcher(iter(range(3)), depth=2)
        assert list(pf) == [0, 1, 2]
        pf.close()
        pf.close()
        assert not pf.thread.is_alive()


# ---------------------------------------------------------------------------
# concurrent readers: the mmap store serves parallel row-range reads
# ---------------------------------------------------------------------------

class TestConcurrentReads:
    def test_concurrent_read_rows_match_serial(self, tmp_path):
        """The async scheduler's slice builders share one EdgeStore: reads
        from many threads must reassemble exactly what a serial reader
        sees, and the shared device ledger must account every word."""
        import threading

        src, dst = rmat_graph(512, 6000, seed=13)
        path = write_edge_store(tmp_path / "g.csr", src, dst,
                                chunk_rows=32, align_words=16)
        dev = BlockDevice(block_words=64, cache_blocks=64)
        store = EdgeStore(path, device=dev)
        serial = EdgeStore(path)
        rng = np.random.default_rng(0)
        windows = [tuple(sorted(rng.integers(0, store.n_nodes, 2)))
                   for _ in range(64)]
        want = [serial.read_rows(lo, hi) for lo, hi in windows]
        got = [None] * len(windows)
        errs = []

        def reader(ids):
            try:
                for i in ids:
                    lo, hi = windows[i]
                    got[i] = store.read_rows(lo, hi)
            except BaseException as e:  # noqa: BLE001
                errs.append(e)

        threads = [threading.Thread(target=reader,
                                    args=(range(k, len(windows), 4),))
                   for k in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errs
        total_words = 0
        for (ip_w, v_w), (ip_g, v_g), (lo, hi) in zip(want, got, windows):
            np.testing.assert_array_equal(ip_w, ip_g)
            np.testing.assert_array_equal(v_w, v_g)
            total_words += len(v_w)
        # the shared ledger saw exactly the words the threads pulled
        assert dev.stats.word_reads == total_words


class TestRowsWiderThanKernel:
    """A minmax-oriented store keeps a hub's whole neighbor list in one
    row. A box with a row wider than the intersect kernel's VMEM envelope
    (``intersect.ops.MAX_K``), or a padded slice over the binary lane's
    cap, must reach the host lane, counted, on every route that ends at
    a padded lane — never the kernel, which refuses it."""

    @pytest.fixture(scope="class")
    def fan(self, tmp_path_factory):
        from repro.kernels.intersect.ops import MAX_K

        leaves = np.arange(1, MAX_K + 9, dtype=np.int64)
        src = np.concatenate([np.zeros(len(leaves), np.int64), leaves[:-1]])
        dst = np.concatenate([leaves, leaves[1:]])
        path = write_edge_store(tmp_path_factory.mktemp("fan") / "fan.csr",
                                src, dst)
        return path, len(leaves) - 1       # one triangle per path edge

    @pytest.mark.parametrize("backend", ["auto", "pallas", "fused"])
    def test_engine_routes_wide_box_to_host(self, fan, backend):
        path, want = fan
        eng = TriangleEngine(store=path, backend=backend,
                             use_pallas_kernels=True)
        assert eng.count() == want
        s = eng.stats
        assert s.n_padded_fallbacks == s.n_host_boxes == 1
        assert s.n_pallas_boxes == s.n_binary_boxes == 0
        assert s.n_fused_fallbacks == (backend == "fused")

    def test_query_engine_routes_wide_pair_to_host(self, fan):
        from repro.query import QueryEngine, patterns

        path, want = fan
        qe = QueryEngine(patterns.triangle(), store=path, backend="pallas",
                         use_pallas_kernels=True)
        assert qe.count() == want
        assert qe.stats.n_kernel_fallbacks >= 1
