"""Public wrapper for the dense triangle-count kernel (pads + dispatch)."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .kernel import triangle_partials_pallas


def _pad2(x: jnp.ndarray, rows: int, cols: int) -> jnp.ndarray:
    p0 = rows - x.shape[0]
    p1 = cols - x.shape[1]
    if p0 or p1:
        x = jnp.pad(x, ((0, p0), (0, p1)))
    return x


def triangle_count(a, b, mask, *, bm: int = 128, bn: int = 128, bk: int = 512,
                   interpret: bool | None = None) -> int:
    """Exact masked dense triangle count Σ mask ⊙ (A Bᵀ) as a Python int,
    on the Pallas kernel (``ref.triangle_count_ref`` is its reference).

    Pads to tile multiples — zero padding is inert (padded rows/cols
    contribute zero paths and zero mask). Per-entry path counts are exact
    in f32 (< 2^24); the int32 per-tile partials are summed in int64 on
    the host."""
    a = jnp.asarray(a)
    b = jnp.asarray(b)
    mask = jnp.asarray(mask)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    d = max(a.shape[1], b.shape[1])
    bk_eff = min(bk, int(np.ceil(d / 128)) * 128)
    d_pad = int(np.ceil(d / bk_eff)) * bk_eff
    nx = int(np.ceil(a.shape[0] / bm)) * bm
    ny = int(np.ceil(b.shape[0] / bn)) * bn
    a = _pad2(a, nx, d_pad)
    b = _pad2(b, ny, d_pad)
    mask = _pad2(mask, nx, ny)
    partials = triangle_partials_pallas(a, b, mask, bm=bm, bn=bn,
                                        bk=bk_eff, interpret=interpret)
    return int(np.asarray(partials, dtype=np.int64).sum())
