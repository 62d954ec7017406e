"""Vectorized LFTJ-Δ in JAX (the TPU-native altitude; DESIGN.md §2.2).

The level-z leapfrog joins of LFTJ-Δ compute |D(x) ∩ D(y)| for every edge
(x, y) of the DAG orientation (paper Alg. 1). We batch *all* of them into a
data-parallel primitive over fixed shapes:

  * neighbor lists padded to K = max out-degree, sorted, sentinel-terminated;
  * per edge, the smaller list is probed into the larger via binary search —
    exactly the min(d_x, d_y) accounting of Thm. 17, so the vectorized form
    inherits the O(|E| · α(G) · log) work bound (the padding waste is bounded
    by degree binning / boxing). Compiled for a TPU, where a per-element
    gather is slow, each probe instead compares against the whole padded
    row (O(K²) lane-parallel work per edge, no gather);
  * a `lax.scan` over edge chunks keeps peak memory at O(chunk · K).

`triangle_count_dense` is the MXU formulation used for dense boxes:
Σ A ⊙ (A Aᵀ) over 0/1 tiles (kernels/triangle_dense implements it in Pallas).
"""

from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

SENTINEL = np.iinfo(np.int32).max


# ---------------------------------------------------------------------------
# host-side graph preparation (numpy)
# ---------------------------------------------------------------------------

def orient_edges(src: np.ndarray, dst: np.ndarray,
                 mode: str = "minmax") -> Tuple[np.ndarray, np.ndarray]:
    """Make the undirected graph a DAG (paper §2.3 G*).

    'minmax'  — (min, max) per edge: the paper's orientation.
    'degree'  — lower-degree endpoint first (ties by id): the standard
                out-degree ≤ O(√|E|) bound; a beyond-paper option that caps
                the padded width K (§Perf hillclimb #1).
    """
    src = np.asarray(src)
    dst = np.asarray(dst)
    keep = src != dst
    src, dst = src[keep], dst[keep]
    if mode == "minmax":
        a = np.minimum(src, dst)
        b = np.maximum(src, dst)
    elif mode == "degree":
        n = int(max(src.max(initial=0), dst.max(initial=0))) + 1
        deg = np.bincount(src, minlength=n) + np.bincount(dst, minlength=n)
        key_s = deg[src] * (n + 1) + src
        key_d = deg[dst] * (n + 1) + dst
        swap = key_s > key_d
        a = np.where(swap, dst, src)
        b = np.where(swap, src, dst)
    else:
        raise ValueError(mode)
    e = np.unique(np.stack([a, b], axis=1), axis=0)
    return e[:, 0], e[:, 1]


def csr_from_edges(src: np.ndarray, dst: np.ndarray,
                   n_nodes: Optional[int] = None) -> Tuple[np.ndarray, np.ndarray]:
    """CSR (indptr, indices) with sorted rows — the TrieArray of E."""
    if n_nodes is None:
        n_nodes = int(max(src.max(initial=-1), dst.max(initial=-1))) + 1
    order = np.lexsort((dst, src))
    src, dst = src[order], dst[order]
    counts = np.bincount(src, minlength=n_nodes)
    indptr = np.concatenate([[0], np.cumsum(counts)]).astype(np.int64)
    return indptr, dst.astype(np.int32)


def pad_neighbors(indptr: np.ndarray, indices: np.ndarray,
                  k: Optional[int] = None) -> np.ndarray:
    """(V, K) padded, sorted neighbor matrix with SENTINEL fill.

    ``k`` < max degree would silently drop neighbors (and miscount every
    downstream intersection), so it is a hard error; rows that must be
    capped belong in ``pad_neighbors_binned``.
    """
    n = len(indptr) - 1
    deg = np.diff(indptr)
    if k is None:
        k = int(deg.max(initial=1))
    k = max(int(k), 1)
    if deg.max(initial=0) > k:
        raise ValueError(
            f"pad_neighbors: k={k} < max degree {int(deg.max())}; this would "
            "silently truncate neighbor lists. Pass k=None or use "
            "pad_neighbors_binned for degree-capped rows.")
    out = np.full((n, k), SENTINEL, dtype=np.int32)
    for_rows = np.repeat(np.arange(n), deg)
    cols = np.arange(len(indices)) - np.repeat(indptr[:-1], deg)
    out[for_rows, cols] = indices
    return out


def pad_neighbors_binned(indptr: np.ndarray, indices: np.ndarray,
                         bin_growth: int = 4):
    """Degree-binned padding: rows grouped into power-of-``bin_growth`` width
    classes so the per-bin K caps the O(V·K_max) padding waste on skewed
    graphs (a hub no longer forces every row to its width).

    Returns ``(row_bin, bins)`` where ``row_bin[v]`` is the bin id of vertex
    v and ``bins[i] = (rows, npad)`` holds the vertex ids in bin i plus
    their (len(rows), K_i) padded neighbor matrix. Vertices with degree 0
    get bin -1 (they cannot participate in any intersection).
    """
    n = len(indptr) - 1
    deg = np.diff(indptr)
    row_bin = np.full(n, -1, dtype=np.int64)
    bins = []
    nonzero = deg > 0
    if nonzero.any():
        widths = []
        k = 1
        kmax = int(deg.max())
        while True:
            widths.append(k)
            if k >= kmax:
                break
            k *= bin_growth
        edges_lo = [w // bin_growth + 1 if w > 1 else 1 for w in widths]
        for b, (klo, khi) in enumerate(zip(edges_lo, widths)):
            rows = np.flatnonzero((deg >= klo) & (deg <= khi))
            if len(rows) == 0:
                bins.append((rows, np.zeros((0, khi), dtype=np.int32)))
                continue
            row_bin[rows] = b
            npad = np.full((len(rows), khi), SENTINEL, dtype=np.int32)
            d = deg[rows]
            rr = np.repeat(np.arange(len(rows)), d)
            cc = np.arange(int(d.sum())) - np.repeat(np.cumsum(d) - d, d)
            src_idx = np.repeat(indptr[rows], d) + cc
            npad[rr, cc] = indices[src_idx]
            bins.append((rows, npad))
    return row_bin, bins


# ---------------------------------------------------------------------------
# jitted intersection primitives
# ---------------------------------------------------------------------------

def _search_hits(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    pos = jnp.clip(jax.vmap(jnp.searchsorted)(b, a), 0, b.shape[1] - 1)
    return jnp.take_along_axis(b, pos, axis=1) == a


def _compare_all_hits(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    return jnp.any(a[:, :, None] == b[:, None, :], axis=2)


def _probe_hits(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """(rows, Ka) mask: ``a[i, j]`` occurs in the sorted SENTINEL-padded
    row ``b[i]`` (SENTINEL never matches). Binary search (one gather per
    step) where the program is compiled for a CPU; for a TPU, whose
    per-element gathers made the binary search ~6x slower than a host
    (scale-16 Graph500 count: 63 s on a v5e chip, 11 s on an 8-core x86
    CPU), a compare against all of ``b[i]`` in one fused reduction
    (1.0 s on the v5e)."""
    hit = jax.lax.platform_dependent(a, b, tpu=_compare_all_hits,
                                     default=_search_hits)
    return hit & (a != SENTINEL)


def _gather_hits(a_mat: jnp.ndarray, b_mat: jnp.ndarray, u: jnp.ndarray,
                 v: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``(a_mat[u], hit mask of a_mat[u] in b_mat[v])``: the row gathers
    under the named scope ``gather`` and the probe under ``probe``, so a
    profile's op metadata says which stage of a program an op is."""
    with jax.named_scope("gather"):
        a, b = a_mat[u], b_mat[v]
    with jax.named_scope("probe"):
        return a, _probe_hits(a, b)


@partial(jax.jit, static_argnames=("chunk",))
def _count_chunked(npad: jnp.ndarray, eu: jnp.ndarray, ev: jnp.ndarray,
                   chunk: int = 2048) -> jnp.ndarray:
    """Σ_edges |N(u) ∩ N(v)| with a scan over fixed-size edge chunks."""
    m = eu.shape[0]
    n_chunks = (m + chunk - 1) // chunk
    pad = n_chunks * chunk - m
    # pad with self-edges on node 0 against an empty sentinel row: count 0
    eu_p = jnp.concatenate([eu, jnp.full((pad,), 0, eu.dtype)])
    ev_p = jnp.concatenate([ev, jnp.full((pad,), 0, ev.dtype)])
    valid = jnp.concatenate([jnp.ones((m,), jnp.int32), jnp.zeros((pad,), jnp.int32)])
    eu_c = eu_p.reshape(n_chunks, chunk)
    ev_c = ev_p.reshape(n_chunks, chunk)
    va_c = valid.reshape(n_chunks, chunk)

    def body(carry, inp):
        u, v, ok = inp
        hit = _gather_hits(npad, npad, u, v)[1] & (ok[:, None] > 0)
        return carry + jnp.sum(hit.astype(carry.dtype)), None

    total, _ = jax.lax.scan(body, jnp.int64(0) if jax.config.jax_enable_x64
                            else jnp.int32(0), (eu_c, ev_c, va_c))
    return total


@partial(jax.jit, static_argnames=("chunk",))
def _count_rows_chunked(a_rows: jnp.ndarray, b_rows: jnp.ndarray,
                        chunk: int = 2048) -> jnp.ndarray:
    """Σ_i |a_rows[i] ∩ b_rows[i]| for pre-gathered row pairs.

    Unlike ``_count_chunked`` the two sides may have different widths
    (degree-binned padding): the narrower row is probed into the wider via
    searchsorted — the min(d_x, d_y) accounting of Thm. 17. Padding rows
    are all-SENTINEL and contribute zero.
    """
    if a_rows.shape[1] > b_rows.shape[1]:  # intersection is symmetric
        a_rows, b_rows = b_rows, a_rows
    e = a_rows.shape[0]
    n_chunks = (e + chunk - 1) // chunk
    pad = n_chunks * chunk - e
    a_p = jnp.concatenate(
        [a_rows, jnp.full((pad, a_rows.shape[1]), SENTINEL, a_rows.dtype)])
    b_p = jnp.concatenate(
        [b_rows, jnp.full((pad, b_rows.shape[1]), SENTINEL, b_rows.dtype)])
    a_c = a_p.reshape(n_chunks, chunk, a_rows.shape[1])
    b_c = b_p.reshape(n_chunks, chunk, b_rows.shape[1])

    def body(carry, inp):
        a, b = inp
        return carry + jnp.sum(_probe_hits(a, b).astype(jnp.int32)), None

    total, _ = jax.lax.scan(body, jnp.int32(0), (a_c, b_c))
    return total


# ---------------------------------------------------------------------------
# listing (enumeration) — bounded output buffer, overflow detected by caller
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("cap", "chunk"))
def _list_chunked(npad: jnp.ndarray, eu: jnp.ndarray, ev: jnp.ndarray,
                  cap: int, chunk: int = 1024, valid=None):
    """Enumerate triangles (u, v, z) with z ∈ N(u) ∩ N(v) for each edge.

    Returns ``(total, buf)`` where ``buf`` is a (cap, 3) int32 buffer holding
    the first ``min(total, cap)`` triangles. ``total`` is always the exact
    count: when ``total > cap`` the buffer overflowed and the caller rescans
    with a larger cap (engine's overflow→rescan protocol). ``valid`` masks
    out pre-padded edge slots (sharded layout); ``None`` = all real.
    """
    m = eu.shape[0]
    k = npad.shape[1]
    n_chunks = (m + chunk - 1) // chunk
    pad = n_chunks * chunk - m
    eu_p = jnp.concatenate([eu, jnp.full((pad,), 0, eu.dtype)])
    ev_p = jnp.concatenate([ev, jnp.full((pad,), 0, ev.dtype)])
    ok0 = jnp.ones((m,), bool) if valid is None else valid.astype(bool)
    valid = jnp.concatenate([ok0, jnp.zeros((pad,), bool)])
    eu_c = eu_p.reshape(n_chunks, chunk)
    ev_c = ev_p.reshape(n_chunks, chunk)
    va_c = valid.reshape(n_chunks, chunk)
    # one spill row past the end of the buffer swallows overflow writes
    buf0 = jnp.zeros((cap + 1, 3), jnp.int32)

    def body(carry, inp):
        total, buf = carry
        u, v, ok = inp
        a, hit = _gather_hits(npad, npad, u, v)   # a: (chunk, K) z's
        hit = hit & ok[:, None]
        flat = hit.reshape(-1)
        zs = a.reshape(-1)
        us = jnp.repeat(u, k).astype(jnp.int32)
        vs = jnp.repeat(v, k).astype(jnp.int32)
        offs = total + jnp.cumsum(flat) - flat    # exclusive prefix position
        slot = jnp.where(flat, jnp.minimum(offs, cap), cap)
        tri = jnp.stack([us, vs, zs], axis=1)
        buf = buf.at[slot].set(tri, mode="drop")
        return (total + jnp.sum(flat), buf), None

    (total, buf), _ = jax.lax.scan(body, (jnp.int32(0), buf0),
                                   (eu_c, ev_c, va_c))
    return total, buf[:cap]


@partial(jax.jit, static_argnames=("chunk",))
def _list_hits(npad: jnp.ndarray, eu: jnp.ndarray, ev: jnp.ndarray,
               chunk: int = 2048) -> jnp.ndarray:
    """(len(eu), K) hit mask: ``out[i, j]`` holds when ``npad[eu[i], j]``
    is also in ``npad[ev[i]]``, i.e. (eu[i], ev[i], npad[eu[i], j]) is a
    triangle. The caller reads the triangles off the mask's nonzeros on the
    host, so the device writes no output buffer: no scatter, no capacity,
    no overflow rescan. ``len(eu)`` must be a multiple of ``chunk``."""
    m = eu.shape[0]
    k = npad.shape[1]

    def body(_, inp):
        u, v = inp
        return None, _gather_hits(npad, npad, u, v)[1]

    _, hits = jax.lax.scan(body, None, (eu.reshape(-1, chunk),
                                        ev.reshape(-1, chunk)))
    return hits.reshape(m, k)


@partial(jax.jit, static_argnames=("cap", "chunk"))
def _list_pairs_chunked(npa: jnp.ndarray, npb: jnp.ndarray,
                        eu: jnp.ndarray, ev: jnp.ndarray,
                        us: jnp.ndarray, vs: jnp.ndarray,
                        cap: int, chunk: int = 1024):
    """Enumerate (us[i], vs[i], z) with z ∈ npa[eu[i]] ∩ npb[ev[i]].

    The degree-binned listing analogue of ``_count_rows_chunked`` +
    ``_list_chunked``: the two padded neighbor matrices may have different
    widths (per-bin K), the narrower side is probed into the wider, and the
    emitted triangle carries the caller-supplied *global* edge endpoints
    ``us``/``vs`` (so no local-row remap is needed afterwards). Padded edge
    slots must reference an all-SENTINEL row on the probed side — they then
    contribute nothing. Returns ``(total, buf)`` with the exact total and a
    (cap, 3) buffer of the first ``min(total, cap)`` triangles.
    """
    if npa.shape[1] > npb.shape[1]:     # z values are symmetric in a∩b
        npa, npb = npb, npa
        eu, ev = ev, eu
    m = eu.shape[0]
    ka = npa.shape[1]
    n_chunks = (m + chunk - 1) // chunk
    pad = n_chunks * chunk - m
    pad_a = jnp.int32(npa.shape[0] - 1)  # caller guarantees SENTINEL row
    eu_c = jnp.concatenate([eu, jnp.full((pad,), pad_a, eu.dtype)]) \
        .reshape(n_chunks, chunk)
    ev_c = jnp.concatenate([ev, jnp.full((pad,), 0, ev.dtype)]) \
        .reshape(n_chunks, chunk)
    us_c = jnp.concatenate([us, jnp.zeros((pad,), us.dtype)]) \
        .reshape(n_chunks, chunk)
    vs_c = jnp.concatenate([vs, jnp.zeros((pad,), vs.dtype)]) \
        .reshape(n_chunks, chunk)
    buf0 = jnp.zeros((cap + 1, 3), jnp.int32)   # spill row swallows overflow

    def body(carry, inp):
        total, buf = carry
        u, v, gu, gv = inp
        a, hit = _gather_hits(npa, npb, u, v)     # a: (chunk, ka) z's
        flat = hit.reshape(-1)
        zs = a.reshape(-1)
        gus = jnp.repeat(gu, ka).astype(jnp.int32)
        gvs = jnp.repeat(gv, ka).astype(jnp.int32)
        offs = total + jnp.cumsum(flat) - flat
        slot = jnp.where(flat, jnp.minimum(offs, cap), cap)
        buf = buf.at[slot].set(jnp.stack([gus, gvs, zs], axis=1),
                               mode="drop")
        return (total + jnp.sum(flat), buf), None

    (total, buf), _ = jax.lax.scan(body, (jnp.int32(0), buf0),
                                   (eu_c, ev_c, us_c, vs_c))
    return total, buf[:cap]


def triangle_count_vectorized(src: np.ndarray, dst: np.ndarray,
                              orientation: str = "minmax",
                              chunk: int = 2048) -> int:
    """End-to-end vectorized LFTJ-Δ triangle count of an undirected graph."""
    a, b = orient_edges(src, dst, orientation)
    indptr, indices = csr_from_edges(a, b)
    npad = pad_neighbors(indptr, indices)
    return int(_count_chunked(jnp.asarray(npad), jnp.asarray(a, jnp.int32),
                              jnp.asarray(b, jnp.int32), chunk=chunk))


# ---------------------------------------------------------------------------
# dense (MXU) formulation
# ---------------------------------------------------------------------------

@jax.jit
def triangle_count_dense(adj: jnp.ndarray) -> jnp.ndarray:
    """Σ A ⊙ (A Aᵀ) for a dense 0/1 DAG adjacency block.

    On TPU this is a masked SYRK on the MXU: |E_box|·d work at 197 TFLOP/s,
    profitable whenever box density is above the MXU/VPU crossover
    (see kernels/triangle_dense for the Pallas tiling and §Perf for the
    crossover measurement).
    """
    a = adj.astype(jnp.float32)
    paths = a @ a.T
    return jnp.sum(a * paths).astype(jnp.int64) if jax.config.jax_enable_x64 \
        else jnp.sum(a * paths).astype(jnp.int32)


def dense_adjacency(src: np.ndarray, dst: np.ndarray, n: int) -> np.ndarray:
    adj = np.zeros((n, n), dtype=np.float32)
    adj[src, dst] = 1.0
    return adj


# ---------------------------------------------------------------------------
# per-box vectorized execution (ties boxing to the TPU path)
# ---------------------------------------------------------------------------

def triangle_count_boxed_vectorized(src: np.ndarray, dst: np.ndarray,
                                    mem_words: int,
                                    orientation: str = "minmax",
                                    dense_threshold: float = 0.05,
                                    chunk: int = 2048) -> Tuple[int, dict]:
    """Boxed execution with the vectorized/dense per-box engines.

    The box plan comes from the paper's probe/provision machinery
    (core.boxing.plan_boxes); per-box backend dispatch (vectorized
    binary-search vs dense MXU vs Pallas), degree binning, and device-mesh
    sharding all live in ``core.engine.TriangleEngine`` — this wrapper is
    the legacy single-host entry point. Returns (count, info).
    """
    from .engine import TriangleEngine

    eng = TriangleEngine(src, dst, mem_words=mem_words,
                         orientation=orientation,
                         dense_threshold=dense_threshold,
                         chunk=chunk, shard=False)
    count = eng.count()
    return count, eng.stats.as_info()
