"""Pallas TPU megakernel: one box's *entire* frontier leapfrog on-device.

The staged device lane (``query/vectorized.py`` + ``kernels/intersect``)
round-trips host<->device once per frontier level — numpy ``searchsorted``
expands, the Pallas kernel intersects, the host filters — so a deep
pattern on a hub box is launch-bound. This kernel runs the whole loop
nest of Veldhuizen's LFTJ for one box as a single ``pallas_call``:

* every atom's box slice is staged into VMEM **once** as a lifted key row
  plus a dense SENTINEL-padded adjacency matrix (split into two f32
  halves so the MXU can gather it, see below);
* the grid walks tiles of the depth-0 frontier (the host-computed key
  intersection of the atoms starting at variable 0);
* each deeper level keeps its candidate frontier in a VMEM scratch
  buffer and iterates it with a ``fori_loop`` that rotates the buffer one
  lane per step — the same rotation idiom as ``kernels/intersect`` — with
  a fixed depth bound compiled from the query pattern;
* membership tests are full-width masked compares against the candidate
  row (a masked ``searchsorted`` without the data-dependent gather, which
  Mosaic does not vectorize);
* the innermost level reduces to a per-tile lane count; per-row counts
  leave the device as one ``(T, 1)`` int32 vector.

One-hot MXU gather
------------------
TPUs have no vectorized dynamic gather, but a row lookup is a matmul:
``onehot = (keys == v)`` then ``onehot @ adjacency``. f32 matmuls carry
24 mantissa bits while vertex ids need 31, so the adjacency matrix is
shipped as two exact f32 halves — ``hi = vals >> 15`` (<= 65536) and
``lo = vals & 0x7fff`` (< 32768) — gathered separately and recombined as
``(hi << 15) | lo``. Each one-hot row has at most one non-zero, so every
dot product is a single exact addend: the gather is bit-exact. Rows whose
key is absent (``sum(onehot) == 0``) come back SENTINEL-filled, which is
precisely the "binding dies here" encoding the pruning steps use, so
deferred key filters need no extra code. Key rows are padded with ``-1``
(never a vertex id) and the frontier with SENTINEL (never gathers).

Program shape
-------------
The kernel body is *generated* from the pattern ``atom_dims`` — the loop
nest is unrolled in Python at trace time, so each (pattern, padded-shape)
pair compiles one program; ``ops.py`` buckets shapes to powers of two to
bound that cache. Counts are int32 per frontier row (a single binding
prefix inside one box never overflows that in practice; the host-side sum
is int64).
"""

from __future__ import annotations

import functools
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

SENTINEL = np.iinfo(np.int32).max
KEY_PAD = -1
VAL_SPLIT = 15  # adjacency value = (hi << VAL_SPLIT) | lo, both exact in f32

# the fixed depth bound of the scratch allocation: patterns with more
# variables fall back to the staged lane (ops.fused_supported)
MAX_DEPTH = 6


def _gather_rows(v, keys, hi, lo):
    """(T, K) adjacency rows of the per-frontier vertices ``v`` ((T, 1)
    int32) via the exact one-hot MXU gather; absent keys -> SENTINEL."""
    onehot = (keys == v).astype(jnp.float32)            # (T, R)
    # HIGHEST: the halves carry up to 16 significant bits, which a single
    # bf16 MXU pass (8 bits) would round
    g_hi = jnp.dot(onehot, hi, precision=jax.lax.Precision.HIGHEST,
                   preferred_element_type=jnp.float32)
    g_lo = jnp.dot(onehot, lo, precision=jax.lax.Precision.HIGHEST,
                   preferred_element_type=jnp.float32)
    g = (g_hi.astype(jnp.int32) << VAL_SPLIT) | g_lo.astype(jnp.int32)
    present = jnp.sum(onehot, axis=1, keepdims=True) > 0.0
    return jnp.where(present, g, SENTINEL)


def _member_mask(a, b):
    """Element-of-same-row membership ``a[i, j] in b[i, :]`` for SENTINEL-
    padded sorted rows — ``kernels/intersect``'s rotation probe, widened
    to unequal row widths by broadcasting one rotated column of ``b``
    against all of ``a`` per step. The hit tile is carried as int32:
    Mosaic cannot legalize a boolean ``scf.for`` carry."""
    kb = b.shape[1]

    def step(_, carry):
        hit, b_rot = carry
        col = b_rot[:, 0:1]
        hit = hit | jnp.where((a == col) & (col != SENTINEL), 1, 0)
        return hit, jnp.roll(b_rot, -1, axis=1)

    hit, _ = jax.lax.fori_loop(
        0, kb, step, (jnp.zeros(a.shape, jnp.int32), b))
    return hit != 0


def starts_only_depths(n_vars: int,
                       atom_dims: Sequence[Tuple[int, int]]) -> List[int]:
    """Intermediate depths whose variable only *starts* atoms: their
    candidate set is a binding-independent key intersection, shipped to
    the kernel as one constant SENTINEL-padded row per depth."""
    seen_second = {sd for _, sd in atom_dims}
    return [d for d in range(1, n_vars - 1) if d not in seen_second]


def make_fused_count_kernel(n_vars: int,
                            atom_dims: Tuple[Tuple[int, int], ...],
                            widths: Tuple[Tuple[int, int], ...],
                            const_widths: Tuple[int, ...],
                            bt: int):
    """Generate the kernel body for one (pattern, padded-shape) pair.

    Ref layout: ``(c0, keys_0, hi_0, lo_0, ..., keys_m, hi_m, lo_m,
    const_0, ..., out, scratch_1, ..., scratch_{n-2})`` — one ``(bt,
    K_d)`` int32 VMEM scratch per intermediate depth holding that level's
    rotating candidate frontier, one ``(8, Kc)`` constant candidate row
    per starts-only depth. ``widths[i] = (R_i, K_i)`` are atom ``i``'s
    padded key count and row width.
    """
    by_second: List[List[int]] = [[] for _ in range(n_vars)]
    by_first: List[List[int]] = [[] for _ in range(n_vars)]
    for ai, (fd, sd) in enumerate(atom_dims):
        by_second[sd].append(ai)
        by_first[fd].append(ai)
    n_atoms = len(atom_dims)
    so_depths = starts_only_depths(n_vars, atom_dims)

    def kernel(*refs):
        c0_ref = refs[0]
        atom_refs = refs[1:1 + 3 * n_atoms]
        const_refs = refs[1 + 3 * n_atoms:
                          1 + 3 * n_atoms + len(so_depths)]
        out_ref = refs[1 + 3 * n_atoms + len(so_depths)]
        scratch = refs[2 + 3 * n_atoms + len(so_depths):]  # depth 1..n-2

        def gathered(ai: int, v):
            # keys ship as an (8, R) sublane-replicated tile (Mosaic's
            # minimum sublane count); one row drives the one-hot compare
            k = atom_refs[3 * ai][0:1, :]
            h = atom_refs[3 * ai + 1][...]
            l = atom_refs[3 * ai + 2][...]
            return _gather_rows(v, k, h, l)

        def expand(d: int, rows: Dict[int, jnp.ndarray]):
            """Depth-d candidates: first bound atom's row, pruned by
            membership in every further bound atom's row; a starts-only
            depth broadcasts its constant candidate row."""
            atoms = by_second[d]
            if not atoms:
                c = const_refs[so_depths.index(d)][0:1, :]
                return jnp.broadcast_to(c, (bt, c.shape[1]))
            cand = rows[atoms[0]]
            for ai in atoms[1:]:
                cand = jnp.where(_member_mask(cand, rows[ai]),
                                 cand, SENTINEL)
            return cand

        def innermost(rows: Dict[int, jnp.ndarray]):
            atoms = by_second[n_vars - 1]
            base = rows[atoms[0]]
            m = jnp.where(base != SENTINEL, 1, 0)
            for ai in atoms[1:]:
                m = m * jnp.where(_member_mask(base, rows[ai]), 1, 0)
            return jnp.sum(m, axis=1, keepdims=True)     # (bt, 1) int32

        def eval_depth(d: int, rows: Dict[int, jnp.ndarray]):
            if d == n_vars - 1:
                return innermost(rows)
            buf = scratch[d - 1]
            buf[...] = expand(d, rows)
            kd = buf.shape[1]

            def body(_, acc):
                v = buf[:, 0:1]
                sub_rows = dict(rows)
                for ai in by_first[d]:
                    sub_rows[ai] = gathered(ai, v)
                acc = acc + jnp.where(v != SENTINEL,
                                      eval_depth(d + 1, sub_rows), 0)
                buf[...] = jnp.roll(buf[...], -1, axis=1)
                return acc

            return jax.lax.fori_loop(
                0, kd, body, jnp.zeros((bt, 1), jnp.int32))

        v0 = c0_ref[...]                                 # (bt, 1)
        rows0 = {ai: gathered(ai, v0) for ai in by_first[0]}
        out_ref[...] = jnp.where(v0 != SENTINEL, eval_depth(1, rows0), 0)

    return kernel


@functools.lru_cache(maxsize=64)
def build_fused_count(n_vars: int,
                      atom_dims: Tuple[Tuple[int, int], ...],
                      widths: Tuple[Tuple[int, int], ...],
                      const_widths: Tuple[int, ...],
                      bt: int, interpret: bool):
    """jit'd ``(c0 (T,1), keys_i (8,R_i), hi_i, lo_i (R_i,K_i)...,
    const_j (8,Kc_j)...) -> (T, 1) int32 per-frontier-row counts``; ``T``
    must be a multiple of the tile ``bt``. Cached per (pattern, bucketed
    shape)."""
    kernel = make_fused_count_kernel(n_vars, atom_dims, widths,
                                     const_widths, bt)
    by_second: List[List[int]] = [[] for _ in range(n_vars)]
    for ai, (_, sd) in enumerate(atom_dims):
        by_second[sd].append(ai)
    so_depths = starts_only_depths(n_vars, atom_dims)
    # depth-d scratch width = the expansion source's padded row width
    scratch_shapes = [(bt, widths[by_second[d][0]][1] if by_second[d]
                       else const_widths[so_depths.index(d)])
                      for d in range(1, n_vars - 1)]

    @jax.jit
    def call(c0, *arrs):
        t = c0.shape[0]
        in_specs = [pl.BlockSpec((bt, 1), lambda i: (i, 0))]
        for (r, k) in widths:
            in_specs += [
                pl.BlockSpec((8, r), lambda i: (0, 0)),
                pl.BlockSpec((r, k), lambda i: (0, 0)),
                pl.BlockSpec((r, k), lambda i: (0, 0)),
            ]
        for kc in const_widths:
            in_specs.append(pl.BlockSpec((8, kc), lambda i: (0, 0)))
        return pl.pallas_call(
            kernel,
            grid=(t // bt,),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((bt, 1), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((t, 1), jnp.int32),
            scratch_shapes=[pltpu.VMEM(s, jnp.int32)
                            for s in scratch_shapes],
            interpret=interpret,
        )(c0, *arrs)

    return call


# ---------------------------------------------------------------------------
# fused listing: the same loop nest as one XLA program
# ---------------------------------------------------------------------------
#
# Listing needs a data-dependent scatter (append each surviving binding at
# its running output offset) — the one primitive the Mosaic lane lacks — so
# the bounded-buffer emission runs the *same* fused loop nest as a single
# jit'd XLA program instead of a pallas_call: still one device invocation
# per box, frontier buffers device-resident, PR-6 overflow->rescan contract
# (exact total + deterministic prefix of the traversal order) preserved.

def _member_sorted(a, b):
    """Per-row membership ``a[i, j] in b[i, :]`` for sorted SENTINEL-padded
    rows — vmapped searchsorted (XLA has the real gather)."""
    pos = jax.vmap(lambda bi, ai: jnp.searchsorted(bi, ai))(b, a)
    pos = jnp.clip(pos, 0, b.shape[1] - 1)
    return (jnp.take_along_axis(b, pos, axis=1) == a) & (a != SENTINEL)


@functools.lru_cache(maxsize=64)
def build_fused_list(n_vars: int,
                     atom_dims: Tuple[Tuple[int, int], ...],
                     cap: int):
    """jit'd ``(c0 (T,), keys_i (R_i,), adj_i (R_i, K_i)...,
    const_j (Kc_j,)...) -> (total int32, buf (cap, n_vars) int32)``.

    ``keys_i`` are SENTINEL-padded sorted key vectors, ``adj_i`` the
    matching SENTINEL-padded adjacency rows, ``const_j`` the constant
    candidate row of each starts-only depth. ``total`` is the exact
    binding count; ``buf`` holds the first ``min(total, cap)`` bindings of
    the fixed traversal order (emission offsets are a running cumsum, so
    the buffer is a true deterministic prefix — rescans extend, never
    reorder)."""
    by_second: List[List[int]] = [[] for _ in range(n_vars)]
    by_first: List[List[int]] = [[] for _ in range(n_vars)]
    for ai, (fd, sd) in enumerate(atom_dims):
        by_second[sd].append(ai)
        by_first[fd].append(ai)
    so_depths = starts_only_depths(n_vars, atom_dims)
    n_atom_arrs = 2 * len(atom_dims)

    @jax.jit
    def call(c0, *arrs):
        def row_of(ai, v):
            keys, adj = arrs[2 * ai], arrs[2 * ai + 1]
            pos = jnp.clip(jnp.searchsorted(keys, v), 0, keys.shape[0] - 1)
            ok = (keys[pos] == v) & (v != SENTINEL)
            return jnp.where(ok[:, None], adj[pos], SENTINEL)

        def expand(d, rows, t):
            atoms = by_second[d]
            if not atoms:
                c = arrs[n_atom_arrs + so_depths.index(d)]
                return jnp.broadcast_to(c[None, :], (t, c.shape[0]))
            cand = rows[atoms[0]]
            for ai in atoms[1:]:
                cand = jnp.where(_member_sorted(cand, rows[ai]),
                                 cand, SENTINEL)
            return cand

        def rec(d, vals, rows, carry):
            t = vals[0].shape[0]
            if d == n_vars - 1:
                f = expand(d, rows, t)                    # (T, K)
                buf, cnt = carry
                t, kk = f.shape
                # a binding that died at an earlier depth (SENTINEL in
                # vals) may still see live innermost rows when those rows
                # don't depend on the dead variable (e.g. a starts-only
                # depth) — gate the emission on the whole binding prefix
                live = jnp.ones((t,), jnp.bool_)
                for v in vals:
                    live = live & (v != SENTINEL)
                mask = ((f != SENTINEL) & live[:, None]).reshape(-1)
                flat = jnp.stack(
                    [jnp.broadcast_to(v[:, None], (t, kk)).reshape(-1)
                     for v in vals] + [f.reshape(-1)], axis=1)
                idx = cnt + jnp.cumsum(mask.astype(jnp.int32)) - 1
                buf = buf.at[jnp.where(mask, idx, cap)].set(
                    flat, mode="drop")
                return buf, cnt + jnp.sum(mask, dtype=jnp.int32)
            cand = expand(d, rows, t)

            def body(_, st):
                cand_rot, buf, cnt = st
                v = cand_rot[:, 0]
                sub_rows = dict(rows)
                for ai in by_first[d]:
                    sub_rows[ai] = row_of(ai, v)
                buf, cnt = rec(d + 1, vals + [v], sub_rows, (buf, cnt))
                return jnp.roll(cand_rot, -1, axis=1), buf, cnt

            _, buf, cnt = jax.lax.fori_loop(
                0, cand.shape[1], body, (cand, *carry))
            return buf, cnt

        buf0 = jnp.full((cap, n_vars), SENTINEL, jnp.int32)
        rows0 = {ai: row_of(ai, c0) for ai in by_first[0]}
        buf, cnt = rec(1, [c0], rows0, (buf0, jnp.int32(0)))
        return cnt, buf

    return call
