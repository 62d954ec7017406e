"""jit'd public wrapper for the leapfrog-intersection kernel."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ledger

from .kernel import SENTINEL, intersect_count_pallas
from .ref import intersect_count_ref

# words of one (rows × K) int32 tile the kernel may hold per grid step:
# two double-buffered input tiles plus the rotating carry and the
# accumulator stay inside v5e's 16 MiB scoped VMEM at (256, 1024) and are
# refused at (256, 4096), so the row tile shrinks as K grows
MAX_TILE_WORDS = 256 * 1024
MAX_K = MAX_TILE_WORDS // 8          # widest row at the minimum 8-row tile

# distinct padded shape signatures seen so far — one jit program each.
# Power-of-two bucketing below bounds this at O(log E · log K) per lane
# instead of one program per exact padded shape; GIL-atomic set.add keeps
# it safe under the multi-worker box scheduler.
_shape_signatures: set = set()


def _pow2(n: int, lo: int = 1) -> int:
    return max(lo, 1 << int(np.ceil(np.log2(max(1, n)))))


def jit_cache_info() -> int:
    """Number of distinct compiled-program shape signatures
    (kernel_bench reports this so cache growth is visible in CI)."""
    return len(_shape_signatures)


def tile_rows(e: int, k: int, be: int = 256) -> int:
    """Rows per grid step for ``e`` rows of padded width ``k``: at most
    ``be``, no taller than the padded batch, and short enough for the
    tile to fit VMEM. All powers of two, so the padded row count is a
    multiple of it."""
    if k > MAX_K:
        raise ValueError(f"row width {k} exceeds the intersect kernel's "
                         f"VMEM envelope (K <= {MAX_K})")
    return min(be, MAX_TILE_WORDS // k, _pow2(max(e, 1), lo=8))


def intersect_count(a, b, *, be: int = 256, use_pallas: bool = True,
                    interpret: bool | None = None) -> jnp.ndarray:
    """Per-row sorted-set intersection counts |a_i ∩ b_i|.

    Pads rows with SENTINEL to a power-of-two lane count (>= 128) and the
    row count to a power-of-two multiple of ``be`` — bucketed shapes, so
    the jit cache holds O(log E · log K) programs instead of one per
    exact padded shape (the ``core/executor.py`` bucketing idiom).
    Padded rows return 0 and are stripped. ``be`` shrinks for small
    batches, so a per-box call from the triangle engine never pads a
    handful of edges up to a full 256-row tile, and for wide rows
    (``tile_rows``)."""
    a = jnp.asarray(a, jnp.int32)
    b = jnp.asarray(b, jnp.int32)
    e, ka = a.shape
    kb = b.shape[1]
    k = _pow2(max(ka, kb, 1), lo=128)
    be = tile_rows(e, k, be)
    ep = _pow2(max(e, 1), lo=be)
    a = jnp.pad(a, ((0, ep - e), (0, k - ka)), constant_values=SENTINEL)
    b = jnp.pad(b, ((0, ep - e), (0, k - kb)), constant_values=SENTINEL)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    _shape_signatures.add((ep, k, be, bool(use_pallas), bool(interpret)))
    if not use_pallas:
        out = intersect_count_ref(a, b)
    else:
        out = intersect_count_pallas(a, b, be=be, interpret=interpret)
    ledger.note(1, bytes_in=2 * ep * k * 4, bytes_out=ep * 4)
    return out[:e]


@functools.partial(jax.jit, static_argnames=("be", "use_pallas",
                                             "interpret"))
def _padded_total(a, b, *, be: int, use_pallas: bool,
                  interpret: bool) -> jnp.ndarray:
    """Σ_i |a_i ∩ b_i| of rows already padded to a bucketed shape, one
    program per shape: padded rows are all-SENTINEL and count 0."""
    if not use_pallas:
        return jnp.sum(intersect_count_ref(a, b))
    return jnp.sum(intersect_count_pallas(a, b, be=be, interpret=interpret))


def _pad_rows(off: np.ndarray, vals: np.ndarray, pos: np.ndarray,
              k: int, n_rows: int) -> np.ndarray:
    """(n_rows, k) SENTINEL-padded value rows gathered from compact CSR
    (``off``/``vals``) at key positions ``pos`` (``len(pos) <= n_rows``)."""
    off = np.asarray(off, dtype=np.int64)
    deg = np.diff(off)[pos]
    out = np.full((n_rows, k), SENTINEL, dtype=np.int32)
    total = int(deg.sum())
    if total:
        idx = np.repeat(off[:-1][pos], deg) \
            + np.arange(total, dtype=np.int64) \
            - np.repeat(np.cumsum(deg) - deg, deg)
        rr = np.repeat(np.arange(len(pos)), deg)
        cc = np.arange(total, dtype=np.int64) \
            - np.repeat(np.cumsum(deg) - deg, deg)
        out[rr, cc] = vals[idx]
    return out


def intersect_count_rows(off_a, vals_a, pos_a, off_b, vals_b, pos_b, *,
                         use_pallas: bool = True,
                         interpret: bool | None = None,
                         chunk: int = 8192) -> int:
    """Σ_i |row_a(pos_a[i]) ∩ row_b(pos_b[i])| from two compact-CSR
    relations — the generic QueryEngine's lowering of its innermost
    two-variable leapfrog onto this kernel.

    Rows are gathered host-side into SENTINEL-padded tiles of bucketed
    shape (power-of-two rows and width, as ``intersect_count``) and fed to
    the kernel in ``chunk``-row batches, so device memory is
    O(chunk · K_box) regardless of the binding-frontier size, and every
    batch of a shape reuses one compiled program. Returns the total as a
    Python int (per-pair counts never leave the device).
    """
    pos_a = np.asarray(pos_a, dtype=np.int64)
    pos_b = np.asarray(pos_b, dtype=np.int64)
    if len(pos_a) == 0:
        return 0
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    deg_a = np.diff(np.asarray(off_a, dtype=np.int64))
    deg_b = np.diff(np.asarray(off_b, dtype=np.int64))
    k = _pow2(max(int(deg_a[pos_a].max(initial=1)),
                  int(deg_b[pos_b].max(initial=1))), lo=128)
    total = 0
    for s in range(0, len(pos_a), chunk):
        pa, pb = pos_a[s:s + chunk], pos_b[s:s + chunk]
        be = tile_rows(len(pa), k)
        ep = _pow2(len(pa), lo=be)
        _shape_signatures.add((ep, k, be, bool(use_pallas), bool(interpret)))
        out = _padded_total(_pad_rows(off_a, vals_a, pa, k, ep),
                            _pad_rows(off_b, vals_b, pb, k, ep), be=be,
                            use_pallas=bool(use_pallas),
                            interpret=bool(interpret))
        ledger.note(1, bytes_in=2 * ep * k * 4, bytes_out=ep * 4)
        total += int(out)
    return total
