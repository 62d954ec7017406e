"""Kernel micro-benchmarks: Pallas (interpret) vs jnp reference.

Wall time in interpret mode is NOT TPU performance (the kernel body runs
in python); the figure of merit here is (a) correctness at benchmark
shapes and (b) the jnp-reference throughput, which IS executed by XLA CPU
and scales with the same arithmetic the TPU kernel performs.

Two structural rows back the PR-7 fused-megakernel claims:

* ``kernel_lftj_fused`` — the fused-vs-staged device-invocation A/B on a
  hub box: both lanes answer the same whole-box triangle join at the same
  VMEM footprint (the staged chunk is sized to the fused kernel's
  measured residency), launches counted by ``repro.kernels.ledger``. The
  >=10x launch reduction is asserted, not just reported — it is shape
  math, not timing, so it is deterministic in CI.
* ``kernel_jit_cache`` — compiled-program cache sizes after the sweep
  (pow2-bucketed shapes keep them logarithmic in input variety).

derived: checks kernel==ref; reports elements/s of the jnp path.

Runs standalone too: ``python -m benchmarks.kernel_bench --smoke --json
kernel-bench.json`` (the CI kernels job).
"""

from __future__ import annotations

import numpy as np
import jax.numpy as jnp

from repro.kernels import ledger
from repro.kernels.embedding_bag.ref import embedding_bag_ref
from repro.kernels.intersect.ref import SENTINEL, intersect_count_ref
from repro.kernels.triangle_dense.ref import triangle_count_ref
from repro.kernels.intersect.ops import (intersect_count,
                                         intersect_count_rows,
                                         jit_cache_info)
from repro.kernels.lftj_fused.ops import (_pow2, _vmem_bytes,
                                          fused_cache_info, fused_count)
from repro.kernels.lftj_fused.ref import fused_ref
from repro.kernels.triangle_dense.ops import triangle_count

from .common import emit, timeit

RNG = np.random.default_rng(0)

TRIANGLE_DIMS = ((0, 1), (0, 2), (1, 2))


def _hub_box(h: int = 64, m: int = 64, link: int = 16):
    """A heavy/light hub box as a compact CSR: ``h`` hubs all adjacent to
    the same ``m`` mid vertices, each mid linked to its next ``link``
    mids — dense hub rows over a sparse tail, the shape the planner's
    heavy_light lane routes to the fused kernel."""
    src, dst = [], []
    for hub in range(h):
        src += [hub] * m
        dst += list(range(h, h + m))
    for mid in range(m):
        stop = min(mid + 1 + link, m)
        src += [h + mid] * (stop - mid - 1)
        dst += list(range(h + mid + 1, h + stop))
    src = np.asarray(src, np.int64)
    dst = np.asarray(dst, np.int64)
    order = np.lexsort((dst, src))
    src, dst = src[order], dst[order]
    keys, counts = np.unique(src, return_counts=True)
    off = np.concatenate([np.zeros(1, np.int64),
                          np.cumsum(counts, dtype=np.int64)])
    return (keys, off, dst.astype(np.int32)), src, dst


def measure_fused_vs_staged(fast: bool = False) -> dict:
    """Device invocations per hub box: fused megakernel vs the staged
    per-chunk intersect lane at equal VMEM footprint.

    The staged chunk is ``fused_vmem_words / (2 * K)`` rows — exactly the
    rows that fit the VMEM the fused kernel actually holds resident — so
    the launch count compares lanes at the same memory budget. Both lanes
    are counted by the kernel ledger and must agree on the exact count.
    """
    csr, src, dst = _hub_box()
    keys, off, vals = csr
    csrs = [csr] * 3

    with ledger.attach() as kl_fused:
        us_fused = timeit(lambda: fused_count(TRIANGLE_DIMS, csrs, 3,
                                              interpret=True), repeats=1)
        total_fused = fused_count(TRIANGLE_DIMS, csrs, 3, interpret=True)

    deg = np.diff(off)
    r = _pow2(len(keys), lo=8)
    k = _pow2(int(deg.max(initial=1)), lo=8)
    vmem = _vmem_bytes(((r, k),) * 3, (), 3, TRIANGLE_DIMS, bt=8)
    chunk = max(256, (vmem // 4) // (2 * k))
    pos_a = np.searchsorted(keys, src)
    pos_b = np.searchsorted(keys, dst)
    ok = keys[np.minimum(pos_b, len(keys) - 1)] == dst
    with ledger.attach() as kl_staged:
        us_staged = timeit(
            lambda: intersect_count_rows(off, vals, pos_a[ok],
                                         off, vals, pos_b[ok],
                                         use_pallas=False, chunk=chunk),
            repeats=1)
        total_staged = intersect_count_rows(off, vals, pos_a[ok],
                                            off, vals, pos_b[ok],
                                            use_pallas=False, chunk=chunk)

    # per-measurement invocation counts (timeit ran warmup + 1 repeat +
    # the checked call = 3 passes through each lane)
    fused_launches = kl_fused.invocations // 3
    staged_launches = kl_staged.invocations // 3
    ratio = staged_launches / max(1, fused_launches)
    assert total_fused == total_staged, (total_fused, total_staged)
    assert ratio >= 10, (
        f"fused lane must cut per-box device invocations >=10x: "
        f"staged={staged_launches} fused={fused_launches}")
    return {
        "match": total_fused == total_staged,
        "fused_launches": fused_launches,
        "staged_launches": staged_launches,
        "launch_ratio": ratio,
        "fused_transfer_bytes": kl_fused.transfer_bytes // 3,
        "staged_transfer_bytes": kl_staged.transfer_bytes // 3,
        "us_fused": us_fused,
        "us_staged": us_staged,
    }


def main(fast: bool = False) -> None:
    # triangle_dense
    n, d = (256, 1024) if fast else (512, 2048)
    a = (RNG.random((n, d)) < 0.05).astype(np.float32)
    m = np.ones((n, n), np.float32)
    aj = jnp.asarray(a)
    mj = jnp.asarray(m)
    want = float(triangle_count_ref(aj, aj, mj))
    got = float(triangle_count(a, a, m))
    us = timeit(lambda: triangle_count_ref(aj, aj, mj).block_until_ready())
    flops = 2 * n * n * d
    emit("kernel_triangle_dense", us,
         f"match={abs(got-want)<1e-2};gflops_ref={flops/us/1e3:.2f}")

    # intersect
    e, k = (2048, 128) if fast else (8192, 256)
    def rows():
        out = np.full((e, k), SENTINEL, np.int32)
        for i in range(e):
            nn = RNG.integers(0, k)
            out[i, :nn] = np.sort(RNG.choice(k * 4, nn, replace=False))
        return out
    A, Bm = rows(), rows()
    Aj, Bj = jnp.asarray(A), jnp.asarray(Bm)
    got = np.asarray(intersect_count(A, Bm, use_pallas=True))
    want = np.asarray(intersect_count_ref(Aj, Bj))
    us = timeit(lambda: intersect_count_ref(Aj, Bj).block_until_ready())
    emit("kernel_intersect", us,
         f"match={bool((got==want).all())};rows_per_s={e/us*1e6:.0f}")

    # fused LFTJ megakernel: correctness at a benchmark shape vs the
    # scalar oracle, then the launch-count A/B vs the staged lane
    csr, _, _ = _hub_box(h=16, m=32, link=8)
    want_n, _ = fused_ref(TRIANGLE_DIMS, [csr] * 3, 3)
    got_n = fused_count(TRIANGLE_DIMS, [csr] * 3, 3, interpret=True)
    us = timeit(lambda: fused_count(TRIANGLE_DIMS, [csr] * 3, 3,
                                    interpret=True))
    emit("kernel_lftj_fused_ref", us, f"match={got_n == want_n}")
    ab = measure_fused_vs_staged(fast)
    emit("kernel_lftj_fused", ab["us_fused"],
         f"match={ab['match']};fused_launches={ab['fused_launches']};"
         f"staged_launches={ab['staged_launches']};"
         f"launch_ratio={ab['launch_ratio']:.1f}")

    # compiled-program cache growth after the sweep above (pow2-bucketed
    # shapes: a handful of programs, not one per input shape)
    fc = fused_cache_info()
    emit("kernel_jit_cache", 0.0,
         f"intersect_signatures={jit_cache_info()};"
         f"fused_count_programs={fc['count_programs']};"
         f"fused_list_programs={fc['list_programs']}")

    # embedding_bag
    v, dd, b, l = (20000, 64, 1024, 8) if fast else (100000, 128, 4096, 8)
    tab = RNG.standard_normal((v, dd)).astype(np.float32)
    idx = RNG.integers(0, v, (b, l)).astype(np.int32)
    tj, ij = jnp.asarray(tab), jnp.asarray(idx)
    us = timeit(lambda: embedding_bag_ref(tj, ij).block_until_ready())
    emit("kernel_embedding_bag", us,
         f"lookups_per_s={b*l/us*1e6:.0f}")


if __name__ == "__main__":
    import argparse
    import json

    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="CI gate: --fast sizes")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write emitted rows as a JSON run record")
    args = ap.parse_args()

    from .common import collected_rows, reset_rows

    reset_rows()
    print("name,us_per_call,derived")
    main(fast=args.fast or args.smoke)
    if args.json:
        with open(args.json, "w") as f:
            json.dump({"suites": ["kernels"], "fast": True,
                       "rows": collected_rows()}, f, indent=2)
        print(f"# wrote {args.json}", flush=True)
