"""Device lane of the generic box join: k-clique counts as XLA programs.

For a k-clique in its natural variable order (the atoms ``E(v_i, v_j)``
for every ``i < j``: the triangle, the four-clique and up to
``MAX_DEPTH`` variables), one box's count is a sum over its depth-0
vertices ``a``. Bind ``v0 = a``; every later variable ``v_j`` then ranges
over ``S_j``, the row of ``a`` in atom ``(0, j)``, and atom ``(i, j)``
relates two of those rows by a 0/1 matrix

    M_ij[p, q] = [S_j[q] is in the row of S_i[p] in atom (i, j)]

which the binary lane's probe builds (``core.lftj_jax._probe_hits``: a
compare against the whole padded row on a TPU, a binary search
elsewhere). The count for ``a`` is the number of (k-1)-cliques of that
(k-1)-partite graph:

* k = 3 (triangle): ``Σ M_12``;
* k = 4 (four-clique): ``Σ M_12 ∘ (M_13 · M_23ᵀ)``, one batched matmul
  on the MXU per chunk of vertices (0/1 entries in bf16, f32
  accumulation: every entry is at most the row width, so exact);
* k ≥ 5: the k-1 case summed over the rows of ``S_1``, each restricting
  the other matrices.

Each atom slice is already cut to the box (rows in its first variable's
range, values in its second's), so the matrices carry the box's ranges
and need no masks. A vertex's count is at most C(w, k-1) for its row
width w; ``width_cap`` keeps that inside int32, and the box total is
summed on the host in int64.

Per box, the host (``lane.pad``) lays every atom slice the programs read
out as one flat CSR and computes, for the values of the depth-0 atoms,
their row positions in the later atoms (the loc entries); the device
(``lane.run``) pads that CSR into SENTINEL rows in one program, then runs
one program per row-width class of the depth-0 vertices, a loop over
fixed-size chunks of them whose start and length are traced. Every
length is a power of two, so a second pass over the same store compiles
nothing.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import lru_cache, partial
from math import comb
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.executor import _pow2
from repro.core.lftj_jax import SENTINEL, _compare_all_hits, _probe_hits
from repro.kernels import ledger
from repro.kernels.lftj_fused.kernel import MAX_DEPTH
from repro.obs.trace import maybe_span

# widest padded row for k = 3: the compare-all probe does w·w·K compares
# per vertex, 2^27 at this width; larger k halve it until C(w, k-1) < 2^31
MAX_WIDTH = 512
# gathered row words one loop step holds (chunk · w · K), 16 MiB of int32
CHUNK_WORDS = 1 << 22
# narrowest per-atom cap on a class's row widths: a floor that keeps the
# number of distinct programs (and compiles) down at little extra work
MIN_TOP = 32
# tests set this to run the TPU's compare-all probe on any platform
COMPARE_ALL = False


def width_cap(k: int) -> int:
    """Widest padded row the lane takes for a k-clique: a vertex's count,
    at most C(w, k-1), must fit an int32."""
    w = MAX_WIDTH
    while comb(w, k - 1) >= 1 << 31:
        w //= 2
    return w


def clique_size(dims: Sequence[Tuple[int, int]], n: int) -> Optional[int]:
    """``n`` when the atoms' ``(first_dim, second_dim)`` pairs are exactly
    the n-clique's ``(i, j)``, ``i < j``, with 3 <= n <= MAX_DEPTH; None
    for any other pattern."""
    if not 3 <= n <= MAX_DEPTH:
        return None
    want = [(i, j) for i in range(n) for j in range(i + 1, n)]
    return n if sorted(dims) == want else None


@lru_cache(maxsize=None)
def _layout(k: int):
    """Entry indices of the stacked padded matrix for a k-clique: the
    probe rows ``S_j`` (atom (0, j), j >= 2), the rows of atom (i, j)
    (1 <= i < j), and the loc rows (the positions of atom (0, i)'s values
    among atom (i, j)'s keys)."""
    pairs = tuple((i, j) for i in range(1, k) for j in range(i + 1, k))
    probes = {j: e for e, j in enumerate(range(2, k))}
    rows = {p: len(probes) + e for e, p in enumerate(pairs)}
    locs = {p: len(probes) + len(pairs) + e for e, p in enumerate(pairs)}
    return pairs, probes, rows, locs


# ---------------------------------------------------------------------------
# device programs
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("width",))
def _pad_rows(deg: jnp.ndarray, vals: jnp.ndarray, *,
              width: int) -> jnp.ndarray:
    """``(len(deg), width)`` SENTINEL-padded rows of a flat CSR: row ``r``
    holds ``deg[r]`` values of ``vals`` in order. Values past the last
    row's end must be SENTINEL and the last row empty: they land there or
    past the width, so every row keeps its SENTINEL tail."""
    at = jnp.arange(vals.shape[0], dtype=jnp.int32)
    start = jnp.cumsum(deg) - deg
    marks = jnp.zeros_like(at).at[start[1:]].add(1, mode="drop")
    rid = jnp.cumsum(marks)
    # each value's column: its distance from the last row start at or
    # before it (a running max, not a gather of start[rid])
    col = at - jax.lax.cummax(jnp.where(marks > 0, at, 0))
    out = jnp.full((deg.shape[0], width), SENTINEL, jnp.int32)
    return out.at[rid, col].set(vals, mode="drop")


def _paths(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """``out[c, p, q] = Σ_l a[c, p, l] · b[c, q, l]`` of 0/1 masks, exact
    in int32: bf16 operands with f32 accumulation on the MXU, f32 on
    other platforms."""
    def mxu(a, b):
        return jnp.einsum("cpl,cql->cpq", a.astype(jnp.bfloat16),
                          b.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)

    def other(a, b):
        return jnp.einsum("cpl,cql->cpq", a.astype(jnp.float32),
                          b.astype(jnp.float32))

    out = jax.lax.platform_dependent(a, b, tpu=mxu, default=other)
    return out.astype(jnp.int32)


def _partite_cliques(m: Dict[Tuple[int, int], jnp.ndarray],
                     size: int) -> jnp.ndarray:
    """Per chunk row, the ``size``-cliques of the ``size``-partite graph
    whose parts ``p < q`` are related by the 0/1 masks ``m[(p, q)]``
    (``(chunk, w_p, w_q)``, parts numbered from 1)."""
    if size == 2:
        return jnp.sum(m[(1, 2)], axis=(1, 2), dtype=jnp.int32)
    if size == 3:
        paths = _paths(m[(1, 3)], m[(2, 3)])
        return jnp.sum(jnp.where(m[(1, 2)], paths, 0), axis=(1, 2))

    def one_row(r, acc):
        first = {q: jax.lax.dynamic_index_in_dim(m[(1, q)], r, axis=1,
                                                 keepdims=False)
                 for q in range(2, size + 1)}
        sub = {(p - 1, q - 1): m[(p, q)] & first[p][:, :, None]
               & first[q][:, None, :]
               for p in range(2, size + 1) for q in range(p + 1, size + 1)}
        return acc + _partite_cliques(sub, size - 1)

    c, w1 = m[(1, 2)].shape[:2]
    return jax.lax.fori_loop(0, w1, one_row, jnp.zeros(c, jnp.int32))


@partial(jax.jit, static_argnames=("k", "rows", "widths", "chunk",
                                   "compare_all"), donate_argnums=(2,))
def _class_counts(padded: jnp.ndarray, pos: jnp.ndarray,
                  counts: jnp.ndarray, start, n, *, k: int, rows: int,
                  widths: Tuple[int, ...], chunk: int,
                  compare_all: bool) -> jnp.ndarray:
    """``counts`` plus, at slots ``start .. start + n - 1``, the k-clique
    count of each depth-0 vertex of one width class, ``chunk`` vertices a
    loop step.

    ``padded`` is the box's stacked matrix (entries of ``rows`` rows, row
    ``rows - 1`` of each all-SENTINEL); ``pos[j - 1, s]`` is slot ``s``'s
    row in atom (0, j), the pad row past the last vertex; ``widths[j - 1]``
    the class's width of ``S_j``. ``start`` and ``n`` are traced, so one
    program serves every class of these widths."""
    pairs, probes, row_e, loc_e = _layout(k)
    probe = _compare_all_hits if compare_all else _probe_hits
    # the entries read at the chunk's own rows: (entry, its atom (0, dim))
    own = [(e, j) for j, e in probes.items()] \
        + [(loc_e[(i, j)], i) for i, j in pairs]
    row_of = {e: r for r, (e, _) in enumerate(own)}

    def cliques(p):
        # whole rows, then the class's widths: on a TPU a gather of part
        # of each row lowers to a loop of one dynamic slice per row
        with jax.named_scope("gather"):
            got = jax.lax.optimization_barrier(jnp.take(
                padded, jnp.stack([e * rows + p[d - 1] for e, d in own]),
                axis=0))                                 # (own, chunk, K)
        s = {j: got[row_of[e], :, :widths[j - 1]]
             for j, e in probes.items()}
        m = {}
        for i, j in pairs:
            wi, wj = widths[i - 1], widths[j - 1]
            loc = jnp.minimum(got[row_of[loc_e[(i, j)]], :, :wi], rows - 1)
            with jax.named_scope("gather"):
                nb = jnp.take(padded, row_e[(i, j)] * rows + loc,
                              axis=0)                    # (chunk, wi, K)
            a = jnp.broadcast_to(s[j][:, None, :], (chunk, wi, wj))
            with jax.named_scope("probe"):
                hit = probe(a.reshape(chunk * wi, wj),
                            nb.reshape(chunk * wi, -1))
            m[(i, j)] = (hit & (a.reshape(hit.shape) != SENTINEL)) \
                .reshape(chunk, wi, wj)
        return _partite_cliques(m, k - 1)

    def step(t, counts):
        at = start + t * chunk
        p = jax.lax.dynamic_slice_in_dim(pos, at, chunk, axis=1)
        slots = at + jnp.arange(chunk, dtype=jnp.int32)
        got = jnp.where(slots < start + n, cliques(p), 0)
        return counts.at[slots].add(got)

    return jax.lax.fori_loop(0, (n + chunk - 1) // chunk, step, counts)


# ---------------------------------------------------------------------------
# host side: one box
# ---------------------------------------------------------------------------

@dataclass
class _Box:
    rows: int                          # rows per entry, a power of two
    width: int                         # padded row width K
    deg: np.ndarray                    # (entries · rows,) int32
    vals: np.ndarray                   # (power of two,) int32
    pos: np.ndarray                    # (k-1, 2 · rows) int32, by class
    classes: List[Tuple[Tuple[int, ...], int, int, int]]
    #                                    widths, chunk, start, n


def box_width(slices) -> int:
    """Padded row width the lane needs for a box's atom slices."""
    return _pow2(max(int(s.deg.max(initial=1)) for s in slices), lo=8)


def _locs(vals: np.ndarray, keys: np.ndarray, pad: int) -> np.ndarray:
    """Position of each value among ``keys``; ``pad`` where absent."""
    if len(keys) == 0:
        return np.full(len(vals), pad, np.int32)
    lo, hi = int(keys[0]), int(keys[-1])
    table = np.full(hi - lo + 2, pad, np.int32)   # last slot: outside
    table[keys - lo] = np.arange(len(keys), dtype=np.int32)
    at = vals.astype(np.int64) - lo
    at[(at < 0) | (at > hi - lo)] = hi - lo + 1
    return table[at]


def _prepare(slc, k: int, cand: np.ndarray) -> _Box:
    """The flat CSR of every entry and the width classes of ``cand``
    (``slc[(i, j)]`` is atom (i, j)'s slice)."""
    pairs, probes, row_e, loc_e = _layout(k)
    rows = _pow2(max(s.n_keys for s in slc.values()) + 1, lo=8)
    width = box_width(slc.values())
    entries = [None] * (len(probes) + 2 * len(pairs))
    for j, e in probes.items():
        entries[e] = (slc[(0, j)], slc[(0, j)].vals)
    for i, j in pairs:
        entries[row_e[(i, j)]] = (slc[(i, j)], slc[(i, j)].vals)
        entries[loc_e[(i, j)]] = (slc[(0, i)], _locs(
            slc[(0, i)].vals, slc[(i, j)].keys, rows - 1))
    deg = np.zeros(len(entries) * rows, np.int32)
    for e, (s, _) in enumerate(entries):
        deg[e * rows:e * rows + s.n_keys] = s.deg
    nnz = int(deg.sum(dtype=np.int64))
    vals = np.full(_pow2(nnz, lo=256), SENTINEL, np.int32)
    if nnz:
        vals[:nnz] = np.concatenate([v for _, v in entries])

    # each vertex's rows in the depth-0 atoms, grouped by its width class
    rows0 = np.stack([np.searchsorted(slc[(0, j)].keys, cand)
                      for j in range(1, k)]).astype(np.int32)
    degs = np.stack([slc[(0, j)].deg[rows0[j - 1]] for j in range(1, k)])
    tops = [_pow2(int(d.max(initial=1)), lo=MIN_TOP) for d in degs]
    cls = np.maximum(8, 1 << np.ceil(np.log2(np.maximum(
        degs.max(axis=0), 1))).astype(np.int64))   # _pow2 of each vertex
    order = np.argsort(cls, kind="stable")
    pos = np.full((k - 1, 2 * rows), rows - 1, np.int32)
    pos[:, :len(cand)] = rows0[:, order]
    classes, start = [], 0
    for w, n in zip(*np.unique(cls, return_counts=True)):
        widths = tuple(min(int(w), t) for t in tops)
        chunk = min(rows, max(1, CHUNK_WORDS // (max(widths) * width)))
        classes.append((widths, chunk, start, int(n)))
        start += int(n)
    return _Box(rows, width, deg, vals, pos, classes)


def count_box(slc, k: int, cand: np.ndarray, tracer=None) -> int:
    """The box's k-clique count on the device; ``slc[(i, j)]`` is atom
    (i, j)'s slice and ``cand`` the depth-0 key intersection (non-empty).
    Spans ``lane.pad``/``lane.put``/``lane.run``; one ``kernels/ledger``
    note per program call."""
    with maybe_span(tracer, "lane.pad"):
        box = _prepare(slc, k, cand)
    with maybe_span(tracer, "lane.put"):
        deg, vals, pos = (jnp.asarray(x) for x in (box.deg, box.vals,
                                                   box.pos))
    with maybe_span(tracer, "lane.run"):
        padded = _pad_rows(deg, vals, width=box.width)
        counts = jnp.zeros(box.pos.shape[1], jnp.int32)
        for widths, chunk, start, n in box.classes:
            counts = _class_counts(padded, pos, counts, start, n, k=k,
                                   rows=box.rows, widths=widths,
                                   chunk=chunk, compare_all=COMPARE_ALL)
        counts = np.asarray(counts)
    ledger.note(1, bytes_in=box.deg.nbytes + box.vals.nbytes)
    ledger.note(len(box.classes), bytes_in=box.pos.nbytes,
                bytes_out=counts.nbytes)
    return int(counts.sum(dtype=np.int64))
