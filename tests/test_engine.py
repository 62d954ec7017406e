"""TriangleEngine: cross-engine equivalence, sharding, listing, padding.

The headline property (ISSUE 1 acceptance): ``TriangleEngine.count()`` and
``.list()`` agree with the scalar ``LeapfrogTriejoin`` reference on every
property-test graph — Erdős–Rényi, power-law (RMAT), planar grid — and on
golden counts for known graphs (K_n → C(n,3), grids → 0), including under
multi-device box sharding (subprocess with forced host devices).
"""

import os
import subprocess
import sys

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import (TrieArray, TriangleEngine, boxed_triangle_count,
                        brute_force_count, engine_count, lftj_triangle_count,
                        measure_dense_crossover, orient_edges, pad_neighbors,
                        pad_neighbors_binned, plan_boxes)
from repro.core.lftj_jax import SENTINEL, _list_chunked, csr_from_edges
from repro.data.graphs import rmat_graph

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


# ---------------------------------------------------------------------------
# graph generators for the property tests
# ---------------------------------------------------------------------------

def er_graph(n, p, seed):
    rng = np.random.default_rng(seed)
    adj = np.triu(rng.random((n, n)) < p, k=1)
    src, dst = np.nonzero(adj)
    return src.astype(np.int64), dst.astype(np.int64)


def grid_graph(n):
    """n x n planar grid: triangle-free by construction."""
    i, j = np.meshgrid(np.arange(n), np.arange(n), indexing="ij")
    v = (i * n + j)
    right = np.stack([v[:, :-1].ravel(), v[:, 1:].ravel()], 1)
    down = np.stack([v[:-1, :].ravel(), v[1:, :].ravel()], 1)
    e = np.concatenate([right, down])
    return e[:, 0], e[:, 1]


def complete_graph(n):
    i, j = np.triu_indices(n, k=1)
    return i.astype(np.int64), j.astype(np.int64)


def reference_count(src, dst):
    a, b = orient_edges(src, dst)
    return lftj_triangle_count(TrieArray.from_edges(a, b))


def reference_list(src, dst):
    out = []
    a, b = orient_edges(src, dst)
    lftj_triangle_count(TrieArray.from_edges(a, b), emit=out.append)
    tris = np.asarray(out, dtype=np.int64).reshape(-1, 3)
    order = np.lexsort((tris[:, 2], tris[:, 1], tris[:, 0]))
    return tris[order]


ENGINE_CONFIGS = [
    dict(),
    dict(mem_words=200),
    dict(degree_bins=True),
    dict(mem_words=200, degree_bins=True),
    dict(shard=True),
    dict(mem_words=200, shard=True),
    dict(mem_words=200, shard=True, degree_bins=True),
    dict(backend="dense"),
    dict(backend="binary"),
    dict(backend="host"),
    dict(orientation="degree"),
    dict(mem_words=200, workers=4),
    dict(mem_words=200, workers=2, backend="host"),
]


class TestCrossEngineEquivalence:
    @settings(max_examples=4, deadline=None)
    @given(st.integers(0, 10_000))
    def test_erdos_renyi(self, seed):
        src, dst = er_graph(30, 0.2, seed)
        want = reference_count(src, dst)
        for kw in ENGINE_CONFIGS:
            assert engine_count(src, dst, **kw) == want, kw

    @settings(max_examples=3, deadline=None)
    @given(st.integers(0, 10_000))
    def test_power_law(self, seed):
        src, dst = rmat_graph(64, 600, seed=seed)
        want = reference_count(src, dst)
        for kw in ENGINE_CONFIGS:
            assert engine_count(src, dst, **kw) == want, kw

    @settings(max_examples=3, deadline=None)
    @given(st.integers(2, 7))
    def test_planar_grid_triangle_free(self, n):
        src, dst = grid_graph(n)
        assert reference_count(src, dst) == 0
        for kw in ENGINE_CONFIGS:
            assert engine_count(src, dst, **kw) == 0, kw

    @settings(max_examples=4, deadline=None)
    @given(st.integers(3, 12))
    def test_golden_complete_graph(self, n):
        src, dst = complete_graph(n)
        want = n * (n - 1) * (n - 2) // 6
        assert reference_count(src, dst) == want
        for kw in ENGINE_CONFIGS:
            assert engine_count(src, dst, **kw) == want, kw

    def test_agrees_with_brute_force(self):
        src, dst = rmat_graph(200, 2500, seed=11)
        want = brute_force_count(src, dst)
        eng = TriangleEngine(src, dst, mem_words=300)
        assert eng.count() == want
        assert eng.stats.n_boxes > 1


class TestListing:
    @settings(max_examples=4, deadline=None)
    @given(st.integers(0, 10_000))
    def test_list_matches_reference(self, seed):
        src, dst = er_graph(25, 0.25, seed)
        want = reference_list(src, dst)
        for kw in [dict(), dict(mem_words=150), dict(shard=True)]:
            eng = TriangleEngine(src, dst, **kw)
            got = eng.list()
            np.testing.assert_array_equal(got, want), kw
            assert eng.stats.n_canonical_fallbacks == 0, kw

    def test_overflow_rescan(self):
        """A deliberately tiny buffer must still produce the full, exact
        listing via the overflow→rescan protocol (the sharded path's
        bounded buffer; the box executor lists off a hit mask)."""
        src, dst = complete_graph(12)
        eng = TriangleEngine(src, dst, shard=True)
        tris = eng.list(capacity=4)
        assert len(tris) == 12 * 11 * 10 // 6
        assert eng.stats.n_rescans >= 1
        np.testing.assert_array_equal(tris, reference_list(src, dst))

    def test_compare_all_probe_matches_binary_search(self):
        """The probe compiled for a TPU (compare against the whole row)
        marks exactly the entries the binary search finds."""
        import jax.numpy as jnp
        from repro.core.lftj_jax import (_compare_all_hits, _probe_hits,
                                         _search_hits)
        rng = np.random.default_rng(0)
        rows = []
        for width in (32, 8):
            m = np.full((64, width), SENTINEL, np.int32)
            for i in range(64):
                r = np.sort(rng.choice(60, rng.integers(0, width + 1),
                                       replace=False))
                m[i, :len(r)] = r
            rows.append(m)
        a, b = rows
        want = np.array([[x != SENTINEL and x in b[i] for x in a[i]]
                         for i in range(len(a))])
        valid = a != SENTINEL
        for probe in (_compare_all_hits, _search_hits):
            got = np.asarray(probe(jnp.asarray(a), jnp.asarray(b)))
            np.testing.assert_array_equal(got & valid, want)
        np.testing.assert_array_equal(
            np.asarray(_probe_hits(jnp.asarray(a), jnp.asarray(b))), want)

    def test_list_chunked_total_exact_on_overflow(self):
        import jax.numpy as jnp
        src, dst = complete_graph(10)
        a, b = orient_edges(src, dst)
        indptr, indices = csr_from_edges(a, b)
        npad = jnp.asarray(pad_neighbors(indptr, indices))
        total, buf = _list_chunked(npad, jnp.asarray(a, jnp.int32),
                                   jnp.asarray(b, jnp.int32), cap=8)
        assert int(total) == 120  # exact count even though only 8 fit
        assert buf.shape == (8, 3)

    def test_listing_empty_graph(self):
        tris = TriangleEngine(np.zeros(0, int), np.zeros(0, int)).list()
        assert tris.shape == (0, 3)


class TestDegreeOrientation:
    @settings(max_examples=8, deadline=None)
    @given(st.integers(0, 10_000))
    def test_outdegree_sqrt_bound(self, seed):
        """degree orientation: if out-deg(v) = d, every out-neighbor has
        degree >= d, so 2|E| >= d^2 — out-degrees are <= sqrt(2|E|)."""
        src, dst = rmat_graph(128, 1500, seed=seed)
        a, b = orient_edges(src, dst, mode="degree")
        m = len(a)
        if m == 0:
            return
        out_deg = np.bincount(a)
        assert out_deg.max() <= np.sqrt(2 * m) + 1

    @settings(max_examples=4, deadline=None)
    @given(st.integers(0, 10_000))
    def test_orientation_invariant_counts(self, seed):
        src, dst = er_graph(30, 0.18, seed)
        want = reference_count(src, dst)
        assert engine_count(src, dst, orientation="degree") == want
        assert engine_count(src, dst, orientation="degree",
                            mem_words=200) == want


class TestBoxingInvariants:
    def _plan(self, seed=3, mem=300):
        src, dst = rmat_graph(128, 2000, seed=seed)
        a, b = orient_edges(src, dst)
        ta = TrieArray.from_edges(a, b)
        return a, b, ta, plan_boxes(ta, mem), mem

    def test_boxes_partition_oriented_edges(self):
        """Every oriented edge falls in exactly one box (the partitioning
        is overlap-free; pruned boxes hold no oriented edge)."""
        a, b, ta, boxes, _ = self._plan()
        covered = np.zeros(len(a), dtype=int)
        for (lx, hx, ly, hy) in boxes:
            covered += ((a >= lx) & (a <= hx) & (b >= ly) & (b <= hy))
        assert (covered == 1).all()

    def test_per_box_provisioned_words_within_budget(self):
        """The x-dimension slice each box provisions fits its budget share
        (4:1 x:y split as in §5), except single-value pinned (spill) boxes
        which are allowed to exceed it by construction."""
        a, b, ta, boxes, mem = self._plan()
        bx = int(mem * 4.0 / 5.0)
        for (lx, hx, ly, hy) in boxes:
            lo = max(lx, int(ta.val[0][0]))
            hi = min(hx, int(ta.val[0][-1]))
            if hi < lo:
                continue
            words = ta.slice_words((), lo, hi)
            assert words <= bx or lo == hi, (lx, hx, words, bx)

    def test_spill_path_exercised(self):
        """A hub star + triangle forces single-value pinned boxes; the
        count must survive the spill handling."""
        hub = np.zeros(80, dtype=int)
        leaves = np.arange(1, 81)
        src = np.concatenate([hub, [1, 1, 2]])
        dst = np.concatenate([leaves, [2, 3, 3]])
        want = brute_force_count(src, dst)
        ta = TrieArray.from_edges(*orient_edges(src, dst))
        cnt, stats = boxed_triangle_count(ta, mem_words=24)
        assert cnt == want
        assert stats.n_spills > 0
        assert engine_count(src, dst, mem_words=24) == want

    def test_plan_single_box_when_budget_fits(self):
        src, dst = er_graph(20, 0.3, seed=0)
        eng = TriangleEngine(src, dst, mem_words=1 << 20)
        eng.count()
        assert eng.stats.n_boxes == 1


class TestPadding:
    def test_pad_neighbors_rejects_truncation(self):
        """Regression: k < max degree used to silently drop neighbors and
        miscount; it must be a hard error now."""
        src = np.array([0, 0, 0, 1])
        dst = np.array([1, 2, 3, 2])
        indptr, indices = csr_from_edges(src, dst)
        with pytest.raises(ValueError, match="truncate"):
            pad_neighbors(indptr, indices, k=2)
        ok = pad_neighbors(indptr, indices, k=5)   # wider than needed: fine
        assert ok.shape[1] == 5
        assert (np.sort(ok[0][ok[0] != SENTINEL]) == [1, 2, 3]).all()

    def test_binned_padding_reconstructs(self):
        src, dst = rmat_graph(64, 800, seed=2)
        a, b = orient_edges(src, dst)
        indptr, indices = csr_from_edges(a, b)
        row_bin, bins = pad_neighbors_binned(indptr, indices)
        deg = np.diff(indptr)
        seen = {}
        for rows, npad in bins:
            for j, v in enumerate(rows):
                seen[v] = npad[j][npad[j] != SENTINEL]
        for v in range(len(deg)):
            if deg[v] == 0:
                assert row_bin[v] == -1
            else:
                np.testing.assert_array_equal(
                    seen[v], indices[indptr[v]:indptr[v + 1]])

    def test_binned_padding_caps_waste(self):
        """One hub must not inflate every row to K = max degree."""
        hub = np.zeros(200, dtype=int)
        leaves = np.arange(1, 201)
        extra_s = np.arange(1, 50)
        extra_d = np.arange(2, 51)
        src = np.concatenate([hub, extra_s])
        dst = np.concatenate([leaves, extra_d])
        a, b = orient_edges(src, dst)
        indptr, indices = csr_from_edges(a, b)
        monolithic = pad_neighbors(indptr, indices)
        _, bins = pad_neighbors_binned(indptr, indices)
        binned_words = sum(npad.size for _, npad in bins)
        assert binned_words < monolithic.size / 10


class TestNonReplicatedSharding:
    """Acceptance: the shard_map path ships per-shard *local* slices, never
    the global (V, K) padded matrix."""

    def test_local_slice_shapes_scale_with_shard(self):
        src, dst = rmat_graph(256, 3000, seed=7)
        want = reference_count(src, dst)
        eng = TriangleEngine(src, dst, mem_words=400, shard=True)
        assert eng.count() == want
        shape = eng.stats.local_npad_shape
        assert shape is not None
        n_shards, R, K = shape
        assert n_shards == len(eng.devices)
        # the rows dimension is exactly the largest shard slice (+ pad row),
        # by construction — not the vertex count
        assert R == max(eng.stats.shard_rows) + 1
        assert all(r <= eng.nv for r in eng.stats.shard_rows)
        # K is the max degree among *referenced* rows, bounded by global K
        deg = np.diff(eng.indptr)
        assert 1 <= K <= int(deg.max())

    def test_listing_local_slices_agree(self):
        src, dst = er_graph(30, 0.25, seed=3)
        eng = TriangleEngine(src, dst, mem_words=200, shard=True)
        np.testing.assert_array_equal(eng.list(), reference_list(src, dst))
        assert eng.stats.local_npad_shape is not None
        assert eng.stats.local_npad_shape[1] <= eng.nv + 1

    def test_binned_shard_path_agrees(self):
        """degree_bins wired into shard_map: per-bin-pair kernels on
        pad_neighbors_binned widths."""
        hub = np.zeros(120, dtype=int)
        leaves = np.arange(1, 121)
        src = np.concatenate([hub, [1, 1, 2, 5, 5, 6]])
        dst = np.concatenate([leaves, [2, 3, 3, 6, 7, 7]])
        want = reference_count(src, dst)
        eng = TriangleEngine(src, dst, mem_words=120, shard=True,
                             degree_bins=True)
        assert eng.count() == want


class TestDegreeBinsFallbacks:
    def test_store_backed_degree_bins_honored_without_warning(self, tmp_path):
        """Store-backed engines honor degree_bins for real now: per-box
        slices are re-laid-out into degree bins inside the streaming
        executor, so the knob neither warns nor changes results — the
        old warn-and-drop fallback is gone."""
        import warnings

        from repro.data.edgestore import write_edge_store

        src, dst = rmat_graph(128, 1500, seed=3)
        path = write_edge_store(tmp_path / "g.csr", src, dst)
        with warnings.catch_warnings():
            warnings.simplefilter("error")   # any warning fails the test
            eng = TriangleEngine(store=path, mem_words=200,
                                 degree_bins=True)
            n = eng.count()
            tris = eng.list()
        assert n == reference_count(src, dst)
        assert len(tris) == n
        np.testing.assert_array_equal(tris, reference_list(src, dst))

    def test_sharded_binned_listing_matches_oracle(self):
        """shard=True + degree_bins=True listing runs the binned per-bin-
        pair listing kernels (no silent unbinned fallback): the triangles
        must match the unsharded reference exactly, and the binned count
        must agree with the listing total."""
        hub = np.zeros(120, dtype=int)
        leaves = np.arange(1, 121)
        src = np.concatenate([hub, [1, 1, 2, 5, 5, 6]])
        dst = np.concatenate([leaves, [2, 3, 3, 6, 7, 7]])
        eng = TriangleEngine(src, dst, mem_words=120, shard=True,
                             degree_bins=True)
        n_binned = eng.count()
        tris = eng.list()                    # binned sharded listing
        assert len(tris) == n_binned
        np.testing.assert_array_equal(tris, reference_list(src, dst))


class TestEngineConfig:
    def test_measured_crossover_is_sane(self):
        thr = measure_dense_crossover()
        assert 0.0 < thr <= 1.0

    def test_crossover_persisted_to_json_cache(self, tmp_path, monkeypatch):
        """Calibration is cached per (backend, device kind) in a JSON file;
        REPRO_CROSSOVER_REMEASURE forces a fresh measurement."""
        import json

        from repro.core import engine as eng_mod

        monkeypatch.setenv("REPRO_CACHE_DIR", str(tmp_path))
        monkeypatch.delenv("REPRO_CROSSOVER_REMEASURE", raising=False)
        eng_mod._crossover_memo.clear()
        thr = measure_dense_crossover(nv=64, repeats=1)
        cache_file = tmp_path / "crossover.json"
        assert cache_file.exists()
        data = json.loads(cache_file.read_text())
        key = [k for k in data if k.endswith(":nv64")]
        assert key and data[key[0]] == thr

        # a planted cache value is trusted (no re-measurement)
        data[key[0]] = 0.123
        cache_file.write_text(json.dumps(data))
        eng_mod._crossover_memo.clear()
        assert measure_dense_crossover(nv=64, repeats=1) == 0.123

        # ... unless a re-measure is forced, which overwrites the entry
        monkeypatch.setenv("REPRO_CROSSOVER_REMEASURE", "1")
        thr2 = measure_dense_crossover(nv=64, repeats=1)
        assert 0.0 < thr2 <= 1.0
        assert json.loads(cache_file.read_text())[key[0]] == thr2

    def test_pallas_band_persisted_alongside_dense_crossover(
            self, tmp_path, monkeypatch):
        """The pallas mid-band calibration lands in the same JSON cache as
        the dense crossover (`:pallas` key suffix) and plumbs through the
        `pallas_threshold="measured"` engine knob."""
        import json

        from repro.core import engine as eng_mod
        from repro.core import measure_pallas_crossover

        monkeypatch.setenv("REPRO_CACHE_DIR", str(tmp_path))
        monkeypatch.delenv("REPRO_CROSSOVER_REMEASURE", raising=False)
        eng_mod._crossover_memo.clear()
        dense = measure_dense_crossover(nv=64, repeats=1)
        band = measure_pallas_crossover(nv=64, repeats=1)
        assert 0.0 < band <= 1.0
        data = json.loads((tmp_path / "crossover.json").read_text())
        dense_keys = [k for k in data if k.endswith(":nv64")]
        band_keys = [k for k in data if k.endswith(":nv64:pallas")]
        assert dense_keys and band_keys       # both entries, one file
        assert data[band_keys[0]] == band
        assert data[dense_keys[0]] == dense

        # a planted band value is trusted and steers the engine knob
        # (the knob measures at the default nv=256 grid)
        data[band_keys[0].replace(":nv64:", ":nv256:")] = 0.031
        (tmp_path / "crossover.json").write_text(json.dumps(data))
        eng_mod._crossover_memo.clear()
        src = np.asarray([0, 0, 1])
        dst = np.asarray([1, 2, 2])
        eng = TriangleEngine(src, dst,
                             pallas_threshold="measured")
        assert eng.pallas_threshold == pytest.approx(0.031)
        # default stays the static crossover/4 band
        eng2 = TriangleEngine(src, dst, dense_threshold=0.2)
        assert eng2.pallas_threshold == pytest.approx(0.05)

    def test_auto_dispatch_routes_midband_to_pallas_when_supported(self):
        """Regression: 'auto' could only ever return dense/binary, leaving
        the Pallas backend dead. With pallas support flagged, mid-density
        boxes (within 4x below the dense crossover) now dispatch to it;
        without support (CPU interpret mode) 'auto' still avoids it."""
        src, dst = complete_graph(8)
        tpu_like = TriangleEngine(src, dst, use_pallas_kernels=True)
        # density 0.2 >> threshold -> dense regardless
        assert tpu_like._pick_backend(200, 32, 32) == "dense"
        # mid band: threshold/4 < 0.02 <= threshold
        assert tpu_like._pick_backend(20, 32, 32) == "pallas"
        # sparse: below the band
        assert tpu_like._pick_backend(5, 100, 100) == "binary"
        cpu = TriangleEngine(src, dst, use_pallas_kernels=False)
        assert cpu._pick_backend(20, 32, 32) == "binary"

    def test_unknown_backend_rejected(self):
        with pytest.raises(ValueError):
            TriangleEngine(np.array([0]), np.array([1]), backend="gpu")

    def test_stats_report_backends(self):
        src, dst = rmat_graph(128, 2000, seed=1)
        eng = TriangleEngine(src, dst, mem_words=300)
        eng.count()
        s = eng.stats
        executed = s.n_dense_boxes + s.n_binary_boxes + s.n_pallas_boxes
        assert 1 <= executed <= s.n_boxes  # empty boxes execute no backend
        assert s.dense_threshold == 0.05


_MULTI_DEVICE_SCRIPT = r"""
import numpy as np, jax
assert len(jax.devices()) == 8, jax.devices()
from repro.core import TriangleEngine, TrieArray, lftj_triangle_count, orient_edges
from repro.data.graphs import rmat_graph

for seed in (0, 5):
    src, dst = rmat_graph(128, 1500, seed=seed)
    a, b = orient_edges(src, dst)
    out = []
    want = lftj_triangle_count(TrieArray.from_edges(a, b), emit=out.append)
    eng = TriangleEngine(src, dst, mem_words=300)
    assert eng.shard and len(eng.devices) == 8
    got = eng.count()
    assert got == want, (seed, got, want)
    assert eng.stats.n_shards == 8
    # non-replicated sharding: per-device arrays are the local slice
    # (rows-referenced x local-K), never the global (V, K) matrix
    shp = eng.stats.local_npad_shape
    assert shp is not None and shp[0] == 8, shp
    assert shp[1] == max(eng.stats.shard_rows) + 1, (shp, eng.stats.shard_rows)
    assert shp[1] <= eng.nv
    tris = eng.list()
    assert len(tris) == want
    ref = np.sort(np.asarray(out, np.int64).reshape(-1, 3), axis=1)
    ref = ref[np.lexsort((ref[:, 2], ref[:, 1], ref[:, 0]))]
    assert (tris == ref).all()
print("MULTI_DEVICE_OK")
"""


class TestMultiDeviceSharding:
    def test_count_and_list_under_8_host_devices(self):
        """Acceptance: count()/list() agree with the reference under
        XLA_FLAGS=--xla_force_host_platform_device_count=8 box sharding."""
        env = dict(os.environ)
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                            + " --xla_force_host_platform_device_count=8")
        env["PYTHONPATH"] = os.path.abspath(SRC) + os.pathsep \
            + env.get("PYTHONPATH", "")
        # the forced-device-count flag only applies to the host platform;
        # pin it so jax never attempts (slow) accelerator backend init
        env["JAX_PLATFORMS"] = "cpu"
        res = subprocess.run([sys.executable, "-c", _MULTI_DEVICE_SCRIPT],
                             capture_output=True, text=True, env=env,
                             timeout=600)
        assert res.returncode == 0, res.stderr[-2000:]
        assert "MULTI_DEVICE_OK" in res.stdout
