"""Public wrapper for the fused per-box LFTJ megakernel.

Takes one box's atom slices as plain compact-CSR triples ``(keys, off,
vals)`` (the kernels layer stays independent of the query layer), pads
them into the kernel's VMEM layout with power-of-two bucketed shapes —
the same jit-cache-bounding idiom as ``core/executor.py`` — and runs the
whole box join as a single device invocation:

* :func:`fused_count`  -> exact count via the Pallas megakernel
  (interpret mode off-TPU);
* :func:`fused_list`   -> (exact total, bounded deterministic-prefix
  binding buffer) via the fused XLA listing program — callers keep the
  PR-6 overflow->rescan protocol unchanged.

:func:`fused_supported` is the static gate: patterns deeper than
``MAX_DEPTH`` variables, with unordered atoms, or with an unbound
intermediate variable (a Cartesian expansion the VMEM-resident frontier
can't bound) fall back to the staged lane, as do boxes whose padded
slices exceed the VMEM budget. Every dispatch notes one device
invocation plus its padded transfer bytes on the attached
:mod:`repro.kernels.ledger`.
"""

from __future__ import annotations

import functools
from typing import List, Optional, Sequence, Tuple

import jax
import numpy as np

from repro.kernels import ledger

from .kernel import (KEY_PAD, MAX_DEPTH, SENTINEL, VAL_SPLIT,
                     build_fused_count, build_fused_list,
                     starts_only_depths)

# v5e's scoped-VMEM limit for one Mosaic kernel (the compiler refuses a
# kernel whose stack exceeds "limit 16.00M"); ``_vmem_bytes`` is an upper
# estimate of that stack, so the gate admits only kernels that compile
VMEM_BUDGET_BYTES = 16 * 1024 * 1024

# Mosaic's lane width: the count kernel pads row widths and constant rows
# to it in interpret mode too, so CPU tests build the compiled layout
LANES = 128

# frontier tile heights tried, largest first: a box too wide for a
# 128-row tile may still fit with a shorter one
TILE_ROWS = (128, 64, 32, 16, 8)


def _pow2(n: int, lo: int = 1) -> int:
    return max(lo, 1 << int(np.ceil(np.log2(max(1, n)))))


class FusedUnsupported(ValueError):
    """Box/pattern outside the fused kernel's static envelope — callers
    fall back to the staged per-level lane."""


def fused_supported(atom_dims: Sequence[Tuple[int, int]],
                    n_vars: int) -> Optional[str]:
    """None if the pattern fits the fused kernel, else the reason."""
    if n_vars < 2:
        return "fused kernel needs at least two variables"
    if n_vars > MAX_DEPTH:
        return (f"pattern depth {n_vars} exceeds the fused kernel's "
                f"MAX_DEPTH={MAX_DEPTH} scratch bound")
    if not atom_dims:
        return "no atoms"
    seen_second = set()
    seen_first = set()
    for fd, sd in atom_dims:
        if not 0 <= fd < sd < n_vars:
            return f"atom dims ({fd}, {sd}) not forward-ordered"
        seen_second.add(sd)
        seen_first.add(fd)
    if (n_vars - 1) not in seen_second:
        return "innermost variable has no bound atom"
    for d in range(1, n_vars - 1):
        # a starts-only depth expands to a binding-independent constant
        # row (fine); a variable touching no atom at all is a free cross
        # product the VMEM-resident frontier can't bound
        if d not in seen_second and d not in seen_first:
            return (f"variable {d} touches no atom — Cartesian "
                    "expansion exceeds the VMEM frontier bound")
    return None


def _check(atom_dims, n_vars) -> None:
    reason = fused_supported(atom_dims, n_vars)
    if reason is not None:
        raise FusedUnsupported(reason)


def _key_intersection(atom_dims, atom_csrs, depth: int) -> np.ndarray:
    """Key intersection of the atoms starting at ``depth`` (host-side:
    depth 0 is the grid axis, starts-only depths ship as constants)."""
    cand: Optional[np.ndarray] = None
    for (fd, _), csr in zip(atom_dims, atom_csrs):
        if fd != depth:
            continue
        keys = np.asarray(csr[0], dtype=np.int64)
        cand = keys if cand is None else cand[np.isin(cand, keys)]
        if len(cand) == 0:
            break
    return cand if cand is not None else np.zeros(0, np.int64)


def _const_rows(atom_dims, atom_csrs, n_vars: int, lane: int,
                sublanes: int):
    """One SENTINEL-padded constant candidate row per starts-only depth
    (``sublanes`` > 1 replicates it into a Mosaic-friendly tile). Returns
    None when any such depth has an empty candidate set — the whole box
    result is empty and no kernel needs to launch."""
    from .kernel import starts_only_depths

    rows: List[np.ndarray] = []
    widths: List[int] = []
    for d in starts_only_depths(n_vars, atom_dims):
        cand = _key_intersection(atom_dims, atom_csrs, d)
        if len(cand) == 0:
            return None, ()
        k = _pow2(len(cand), lo=lane)
        if sublanes > 1:
            row = np.full((sublanes, k), SENTINEL, np.int32)
            row[:, :len(cand)] = cand.astype(np.int32)
        else:
            row = np.full(k, SENTINEL, np.int32)
            row[:len(cand)] = cand.astype(np.int32)
        rows.append(row)
        widths.append(k)
    return rows, tuple(widths)


def _dense_rows(csr, r: int, k: int) -> np.ndarray:
    """(r, k) SENTINEL-padded dense adjacency from a compact CSR."""
    keys, off, vals = csr
    off = np.asarray(off, dtype=np.int64)
    vals = np.asarray(vals, dtype=np.int32)
    deg = np.diff(off)
    out = np.full((r, k), SENTINEL, dtype=np.int32)
    total = int(deg.sum())
    if total:
        rr = np.repeat(np.arange(len(keys)), deg)
        cc = np.arange(total, dtype=np.int64) \
            - np.repeat(np.cumsum(deg) - deg, deg)
        out[rr, cc] = vals
    return out


def _count_widths(atom_csrs) -> Tuple[Tuple[int, int], ...]:
    """Each atom's padded (R, K) in the count kernel's layout."""
    return tuple(
        (_pow2(len(keys), lo=8),
         _pow2(int(np.diff(np.asarray(off, dtype=np.int64)).max(initial=1)),
               lo=LANES))
        for keys, off, _ in atom_csrs)


def _count_arrays(atom_csrs, widths):
    """Pad every atom into the count kernel's layout: keys (8, R) int32
    (KEY_PAD-padded, sublane-replicated), hi/lo (R, K) f32 halves."""
    arrs: List[np.ndarray] = []
    in_bytes = 0
    for csr, (r, k) in zip(atom_csrs, widths):
        keys = csr[0]
        kp = np.full((8, r), KEY_PAD, np.int32)
        kp[:, :len(keys)] = np.asarray(keys, dtype=np.int32)
        dense = _dense_rows(csr, r, k)
        hi = (dense >> VAL_SPLIT).astype(np.float32)
        lo = (dense & ((1 << VAL_SPLIT) - 1)).astype(np.float32)
        arrs += [kp, hi, lo]
        in_bytes += kp.nbytes + hi.nbytes + lo.nbytes
    return arrs, in_bytes


def _vmem_bytes(widths, const_widths, n_vars, atom_dims, bt: int) -> int:
    """Upper estimate of the compiler's scoped-VMEM stack for one kernel.

    Fitted to compiles for a described v5e over triangle and four-clique
    widths up to (R, K) = (2048, 1024) and ``bt`` in {8, 128}, with no
    under-estimate: the resident atom slices (keys + hi/lo halves, 1.25x
    for layout copies), the frontier scratch and constant rows, and a
    per-tile working set that grows with the one-hot width R and the row
    width K of every gather (``tests/test_chip_compile.py`` re-checks the
    gate against the compiler)."""
    from .kernel import starts_only_depths

    n_atoms = len(widths)
    resident = sum(8 * r * 4 + 2 * r * k * 4 for r, k in widths)
    total = resident * 5 // 4
    by_second = [[] for _ in range(n_vars)]
    for ai, (_, sd) in enumerate(atom_dims):
        by_second[sd].append(ai)
    so_depths = starts_only_depths(n_vars, atom_dims)
    for d in range(1, n_vars - 1):                  # frontier scratch
        total += bt * (widths[by_second[d][0]][1] if by_second[d]
                       else const_widths[so_depths.index(d)]) * 4
    for kc in const_widths:                         # constant rows
        total += 8 * kc * 4
    r_max = max(r for r, _ in widths)
    k_max = max([k for _, k in widths] + list(const_widths))
    total += bt * 4 * ((3 * n_atoms + 2) * r_max + (2 * n_atoms + 2) * k_max)
    return total


def fused_count(atom_dims: Sequence[Tuple[int, int]],
                atom_csrs: Sequence[Tuple[np.ndarray, np.ndarray,
                                          np.ndarray]],
                n_vars: int, *, interpret: Optional[bool] = None) -> int:
    """Exact box-join count in ONE device invocation.

    Raises :class:`FusedUnsupported` when the pattern or the padded box
    falls outside the kernel's envelope (caller falls back to the staged
    lane). An empty depth-0 frontier returns 0 without launching."""
    atom_dims = tuple(tuple(d) for d in atom_dims)
    _check(atom_dims, n_vars)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    c0 = _key_intersection(atom_dims, atom_csrs, 0)
    if len(c0) == 0:
        return 0
    consts, const_widths = _const_rows(atom_dims, atom_csrs, n_vars,
                                       LANES, sublanes=8)
    if consts is None:                  # a starts-only depth is empty
        return 0
    # gate on the padded widths before padding anything: a hub row can
    # make the padded slices larger than host memory
    widths = _count_widths(atom_csrs)
    bt = min(8 if interpret else TILE_ROWS[0], _pow2(len(c0), lo=8))
    if not interpret:
        fits = [t for t in TILE_ROWS if t <= bt and _vmem_bytes(
            widths, const_widths, n_vars, atom_dims, t) <= VMEM_BUDGET_BYTES]
        if not fits:
            raise FusedUnsupported(
                "padded box slices exceed the VMEM budget")
        bt = fits[0]
    arrs, in_bytes = _count_arrays(atom_csrs, widths)
    t = _pow2(len(c0), lo=bt)
    c0p = np.full((t, 1), SENTINEL, np.int32)
    c0p[:len(c0), 0] = c0
    call = build_fused_count(n_vars, atom_dims, widths, const_widths,
                             bt, bool(interpret))
    out = call(c0p, *arrs, *consts)
    in_bytes += sum(c.nbytes for c in consts)
    ledger.note(1, bytes_in=in_bytes + c0p.nbytes, bytes_out=t * 4)
    return int(np.asarray(out, dtype=np.int64)[:len(c0), 0].sum())


def _list_arrays(atom_csrs):
    """Listing-program layout: keys (R,) int32 SENTINEL-padded sorted,
    adjacency (R, K) int32 SENTINEL-padded (XLA gathers directly)."""
    arrs: List[np.ndarray] = []
    in_bytes = 0
    for csr in atom_csrs:
        keys, off, _ = csr
        deg = np.diff(np.asarray(off, dtype=np.int64))
        r = _pow2(len(keys), lo=8)
        k = _pow2(int(deg.max(initial=1)), lo=8)
        kp = np.full(r, SENTINEL, np.int32)
        kp[:len(keys)] = np.asarray(keys, dtype=np.int32)
        arrs += [kp, _dense_rows(csr, r, k)]
        in_bytes += kp.nbytes + arrs[-1].nbytes
    return arrs, in_bytes


def fused_list(atom_dims: Sequence[Tuple[int, int]],
               atom_csrs: Sequence[Tuple[np.ndarray, np.ndarray,
                                         np.ndarray]],
               n_vars: int, capacity: int, *,
               interpret: Optional[bool] = None,
               ) -> Tuple[int, np.ndarray]:
    """(exact total, first ``min(total, capacity)`` bindings) in ONE
    device invocation. The returned rows are the deterministic prefix of
    the program's fixed traversal order — ``total > capacity`` signals
    overflow and the caller rescans with a capacity that holds ``total``.
    """
    atom_dims = tuple(tuple(d) for d in atom_dims)
    _check(atom_dims, n_vars)
    capacity = int(capacity)
    if capacity < 1:
        raise ValueError("capacity must be >= 1")
    c0 = _key_intersection(atom_dims, atom_csrs, 0)
    if len(c0) == 0:
        return 0, np.zeros((0, n_vars), np.int64)
    consts, _ = _const_rows(atom_dims, atom_csrs, n_vars,
                            lane=8, sublanes=1)
    if consts is None:                  # a starts-only depth is empty
        return 0, np.zeros((0, n_vars), np.int64)
    arrs, in_bytes = _list_arrays(atom_csrs)
    t = _pow2(len(c0), lo=8)
    c0p = np.full(t, SENTINEL, np.int32)
    c0p[:len(c0)] = c0
    cap = _pow2(capacity, lo=8)
    call = build_fused_list(n_vars, atom_dims, cap)
    cnt, buf = call(c0p, *arrs, *consts)
    in_bytes += sum(c.nbytes for c in consts)
    ledger.note(1, bytes_in=in_bytes + c0p.nbytes,
                bytes_out=cap * n_vars * 4 + 4)
    total = int(cnt)
    take = min(total, capacity)
    rows = np.asarray(buf, dtype=np.int64)[:take]
    return total, rows


def fused_cache_info() -> dict:
    """Compiled-program cache sizes (kernel_bench reports these next to
    the intersect kernel's shape-signature count)."""
    return {
        "count_programs": build_fused_count.cache_info().currsize,
        "list_programs": build_fused_list.cache_info().currsize,
    }
