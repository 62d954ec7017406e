"""Per-kernel validation: shape/dtype sweeps, Pallas (interpret) vs ref.py
oracle vs numpy ground truth."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from hypothesis import given, settings, strategies as st

from repro.kernels.triangle_dense.ops import triangle_count
from repro.kernels.triangle_dense.ref import triangle_count_ref
from repro.kernels.intersect.ops import intersect_count
from repro.kernels.intersect.ref import SENTINEL, intersect_count_ref
from repro.kernels.embedding_bag.ops import embedding_bag
from repro.kernels.embedding_bag.ref import embedding_bag_ref

RNG = np.random.default_rng(0)


class TestTriangleDense:
    @pytest.mark.parametrize("nx,ny,d", [(64, 64, 128), (100, 140, 300),
                                         (128, 128, 512), (1, 7, 64),
                                         (257, 129, 640)])
    def test_shapes(self, nx, ny, d):
        a = (RNG.random((nx, d)) < 0.15).astype(np.float32)
        b = (RNG.random((ny, d)) < 0.15).astype(np.float32)
        m = (RNG.random((nx, ny)) < 0.3).astype(np.float32)
        got = float(triangle_count(a, b, m))
        want = float(np.sum(m * (a @ b.T)))
        np.testing.assert_allclose(got, want, rtol=1e-5)

    @pytest.mark.parametrize("dtype", [np.float32, np.int32, bool])
    def test_dtypes(self, dtype):
        a = (RNG.random((64, 128)) < 0.2).astype(dtype)
        b = (RNG.random((64, 128)) < 0.2).astype(dtype)
        m = (RNG.random((64, 64)) < 0.3).astype(dtype)
        got = float(triangle_count(a, b, m))
        want = float(np.sum(m.astype(np.float64) *
                            (a.astype(np.float64) @ b.astype(np.float64).T)))
        np.testing.assert_allclose(got, want, rtol=1e-5)

    def test_against_ref_module(self):
        a = (RNG.random((96, 256)) < 0.1).astype(np.float32)
        b = (RNG.random((96, 256)) < 0.1).astype(np.float32)
        m = np.ones((96, 96), np.float32)
        got = float(triangle_count(a, b, m))
        ref = float(triangle_count_ref(jnp.asarray(a), jnp.asarray(b),
                                       jnp.asarray(m)))
        np.testing.assert_allclose(got, ref, rtol=1e-6)

    def test_block_size_sweep(self):
        a = (RNG.random((256, 512)) < 0.1).astype(np.float32)
        m = np.ones((256, 256), np.float32)
        want = float(np.sum(m * (a @ a.T)))
        for bm, bn, bk in [(128, 128, 512), (128, 128, 128), (256, 128, 256)]:
            got = float(triangle_count(a, a, m, bm=bm, bn=bn, bk=bk))
            np.testing.assert_allclose(got, want, rtol=1e-5)


def sorted_rows(e, k, hi, rng):
    out = np.full((e, k), SENTINEL, np.int32)
    for i in range(e):
        n = rng.integers(0, min(k, hi) + 1)
        out[i, :n] = np.sort(rng.choice(hi, size=n, replace=False))
    return out


class TestIntersect:
    @pytest.mark.parametrize("e,k,hi", [(10, 8, 50), (50, 40, 200),
                                        (256, 128, 500), (3, 130, 1000)])
    def test_counts(self, e, k, hi):
        rng = np.random.default_rng(e * k)
        a = sorted_rows(e, k, hi, rng)
        b = sorted_rows(e, k, hi, rng)
        got = np.asarray(intersect_count(a, b, use_pallas=True))
        want = np.asarray([len(set(a[i][a[i] != SENTINEL]) &
                               set(b[i][b[i] != SENTINEL]))
                           for i in range(e)])
        np.testing.assert_array_equal(got, want)

    def test_ref_agrees(self):
        rng = np.random.default_rng(7)
        a = sorted_rows(64, 32, 100, rng)
        b = sorted_rows(64, 32, 100, rng)
        got = np.asarray(intersect_count(a, b, use_pallas=True))
        ref = np.asarray(intersect_count(a, b, use_pallas=False))
        np.testing.assert_array_equal(got, ref)

    def test_empty_rows(self):
        a = np.full((8, 16), SENTINEL, np.int32)
        b = np.full((8, 16), SENTINEL, np.int32)
        got = np.asarray(intersect_count(a, b))
        np.testing.assert_array_equal(got, np.zeros(8, np.int32))

    @settings(max_examples=15, deadline=None)
    @given(st.integers(1, 40), st.integers(1, 33))
    def test_property_random_shapes(self, e, k):
        rng = np.random.default_rng(e * 31 + k)
        a = sorted_rows(e, k, 60, rng)
        b = sorted_rows(e, k, 60, rng)
        got = np.asarray(intersect_count(a, b))
        want = np.asarray([len(set(a[i][a[i] != SENTINEL]) &
                               set(b[i][b[i] != SENTINEL]))
                           for i in range(e)])
        np.testing.assert_array_equal(got, want)

    def test_jit_cache_stays_bucketed(self):
        """Pow2-bucketed pad shapes: a sweep of nearby (E, K) inputs must
        reuse a handful of compiled signatures, not one per exact shape —
        the unbounded-cache leak this bucketing closed."""
        from repro.kernels.intersect.ops import jit_cache_info
        rng = np.random.default_rng(3)
        before = jit_cache_info()
        for e in range(65, 97, 4):                 # all bucket to ep=128
            for k in (129, 140, 200, 255):         # all bucket to k=256
                a = sorted_rows(e, k, 500, rng)
                b = sorted_rows(e, k, 500, rng)
                got = np.asarray(intersect_count(a, b))
                want = np.asarray([len(set(a[i][a[i] != SENTINEL]) &
                                       set(b[i][b[i] != SENTINEL]))
                                   for i in range(e)])
                np.testing.assert_array_equal(got, want)
        assert jit_cache_info() - before <= 2


class TestEmbeddingBag:
    @pytest.mark.parametrize("mode", ["onehot", "dma"])
    @pytest.mark.parametrize("v,d,b,l", [(100, 16, 8, 3), (1000, 64, 32, 7),
                                         (512, 128, 16, 1)])
    def test_modes_shapes(self, mode, v, d, b, l):
        rng = np.random.default_rng(v + b)
        tab = rng.standard_normal((v, d)).astype(np.float32)
        idx = rng.integers(0, v + 1, (b, l)).astype(np.int32)  # v == PAD
        got = np.asarray(embedding_bag(tab, idx, mode=mode))
        want = np.asarray(embedding_bag_ref(jnp.asarray(tab), jnp.asarray(idx)))
        np.testing.assert_allclose(got, want, atol=1e-4)

    def test_all_pad(self):
        tab = RNG.standard_normal((50, 8)).astype(np.float32)
        idx = np.full((4, 5), 50, np.int32)
        got = np.asarray(embedding_bag(tab, idx, mode="onehot"))
        np.testing.assert_allclose(got, np.zeros((4, 8)), atol=0)

    def test_weighted_ref(self):
        tab = RNG.standard_normal((30, 4)).astype(np.float32)
        idx = RNG.integers(0, 30, (6, 3)).astype(np.int32)
        w = RNG.random((6, 3)).astype(np.float32)
        got = np.asarray(embedding_bag_ref(jnp.asarray(tab), jnp.asarray(idx),
                                           jnp.asarray(w)))
        want = np.einsum("bld,bl->bd", tab[idx], w)
        np.testing.assert_allclose(got, want, rtol=1e-5)
