"""Pallas TPU kernel: masked-SYRK triangle count over dense 0/1 tiles.

count = Σ mask ⊙ (A Bᵀ): A (nx,d) = x-slice rows, B (ny,d) = y-slice rows,
mask (nx,ny) = in-box edge indicator. This is the MXU formulation of the
per-box level-z leapfrog joins (DESIGN.md §2): for dense boxes a bitmap
matmul beats per-edge sorted intersection.

Grid: (nx/bm, ny/bn, d/bk) with the contraction axis innermost so A/B tile
DMAs double-buffer across k-steps. Each (i,j) cell accumulates
paths += A_tile @ B_tileᵀ in an fp32 VMEM scratch (exact: every entry is
a path count ≤ d < 2^24), then applies the mask once at k == nsteps-1 and
folds the masked (bm, bn) tile into an aligned (8, bn) int32 partial by
summing its 8-row groups. The wrapper sums the (nx/bm·8, ny/bn·bn) partial
grid on the host in int64, so a box count above 2^24 (or 2^31) stays exact.

VMEM per cell @ (bm,bn,bk)=(128,128,512): 2·(bm·bk + bn·bk + bm·bn)·4B
(double-buffered inputs) + bm·bn·4B scratch ≈ 1.2 MiB — far under the
scoped VMEM limit; bk=512 keeps the MXU k-dim pipelined at its native 128
multiple.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# sublane height of one partial block: the (8, 128) int32 tile
PARTIAL_ROWS = 8


def _tri_kernel(a_ref, b_ref, m_ref, out_ref, acc_ref, *, nsteps_k):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a = a_ref[...]                                       # (bm, bk)
    b = b_ref[...]                                       # (bn, bk)
    acc_ref[...] += jax.lax.dot_general(
        a, b, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)              # MXU matmul

    @pl.when(k == nsteps_k - 1)
    def _finish():
        masked = (m_ref[...] * acc_ref[...]).astype(jnp.int32)
        bm, bn = masked.shape
        out_ref[...] = jnp.sum(
            masked.reshape(bm // PARTIAL_ROWS, PARTIAL_ROWS, bn), axis=0)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def triangle_partials_pallas(a: jnp.ndarray, b: jnp.ndarray,
                             mask: jnp.ndarray, bm: int = 128,
                             bn: int = 128, bk: int = 512,
                             interpret: bool = False) -> jnp.ndarray:
    """(nx/bm·8, ny/bn·bn) int32 partial grid whose sum is the count.

    All dims must be multiples of block sizes (ops.py pads); ``bm`` a
    multiple of 8 and ``bn`` of 128."""
    nx, d = a.shape
    ny = b.shape[0]
    assert nx % bm == 0 and ny % bn == 0 and d % bk == 0, (nx, ny, d, bm, bn, bk)
    assert bm % PARTIAL_ROWS == 0, bm
    grid = (nx // bm, ny // bn, d // bk)
    return pl.pallas_call(
        functools.partial(_tri_kernel, nsteps_k=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bn, bk), lambda i, j, k: (j, k)),
            pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        ],
        out_specs=pl.BlockSpec((PARTIAL_ROWS, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((grid[0] * PARTIAL_ROWS, ny),
                                       jnp.int32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(a.astype(jnp.float32), b.astype(jnp.float32), mask.astype(jnp.float32))
