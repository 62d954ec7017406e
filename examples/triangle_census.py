"""End-to-end driver (the paper's kind: out-of-core graph query serving).

Pipeline, exactly as a production deployment would run it:

  1. ingest a large RMAT graph, orient + build the TrieArray (O(sort));
  2. plan boxes against a memory budget (the paper's probe/provision);
  3. execute box-parallel with the fault-tolerant scheduler (a simulated
     worker dies mid-run; a straggler gets its box stolen) — results are
     exact because boxes are idempotent;
  4. per-node triangle counts become clustering-coefficient features;
  5. a GCN consumes the features for a few training steps (shared CSR
     substrate: the same arrays feed message passing).

    PYTHONPATH=src python examples/triangle_census.py [--edges 200000]
"""

import argparse
import time

import numpy as np

from repro.core import TriangleEngine
from repro.core.lftj_jax import _count_chunked
from repro.data.graphs import rmat_graph
from repro.launch.compile_cache import enable_compile_cache
from repro.runtime.straggler import BoxScheduler, fail_worker


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--nodes", type=int, default=1 << 13)
    ap.add_argument("--edges", type=int, default=200_000)
    ap.add_argument("--workers", type=int, default=4)
    ap.add_argument("--mem-frac", type=float, default=0.15)
    args = ap.parse_args()
    enable_compile_cache()

    t0 = time.time()
    src, dst = rmat_graph(args.nodes, args.edges, seed=0)
    eng = TriangleEngine(src, dst, shard=False)  # scheduler plays the mesh
    a, b = eng.a, eng.b
    print(f"[ingest] {len(a)} edges -> CSR over {eng.nv} nodes "
          f"({time.time()-t0:.1f}s)")

    eng.mem_words = int((len(a) * 2 + eng.nv) * args.mem_frac)
    boxes = eng.plan()
    print(f"[plan]   {len(boxes)} boxes @ {args.mem_frac:.0%} memory budget")

    import jax.numpy as jnp

    def solve(box):
        eu, ev, _, _ = eng._box_edges(box)
        if len(eu) == 0:
            return 0
        return int(_count_chunked(eng.npad, jnp.asarray(eu, jnp.int32),
                                  jnp.asarray(ev, jnp.int32), chunk=1024))

    sched = BoxScheduler(boxes, n_workers=args.workers, steal_after_s=0.0)
    # chaos: worker 0 grabs work and dies
    sched.next_for(0, now=0.0)
    n_requeued = fail_worker(sched, 0)
    t1 = time.time()
    while not sched.all_done():
        for w in range(1, args.workers):
            t = sched.next_for(w, now=1e9)
            if t is not None:
                sched.complete(w, t.box_id, solve(t.payload))
    total = sum(sched.results())
    print(f"[boxes]  {total} triangles in {time.time()-t1:.1f}s on "
          f"{args.workers - 1} surviving workers "
          f"(1 worker killed, {n_requeued} boxes re-queued, "
          f"{sched.duplicates} steals)")
    check = eng.count()  # same engine, in-process (sharded if multi-device)
    assert total == check, (total, check)
    print(f"[verify] matches TriangleEngine.count(): {check} "
          f"({eng.stats.n_dense_boxes}/{eng.stats.n_boxes} dense boxes, "
          f"{eng.stats.n_shards} shard(s))")

    # degree + global clustering features -> GCN (shared CSR substrate)
    deg = np.bincount(np.concatenate([a, b]), minlength=eng.nv)
    cc = np.minimum(total * 3 / max(1, len(a)), 1.0) * np.ones(eng.nv)
    feats = np.stack([deg / max(1, deg.max()), cc,
                      np.log1p(deg)], 1).astype(np.float32)

    import dataclasses
    import jax
    from repro.configs import get_arch
    from repro.models import gnn as G, layers as L
    from repro.optim import adamw
    L.set_dtypes(jnp.float32, jnp.float32)
    cfg = dataclasses.replace(get_arch("gcn-cora").smoke_config,
                              d_in=3, d_out=2)
    params = G.init_params(cfg, jax.random.PRNGKey(0))
    n = feats.shape[0]
    batch = {"node_feat": jnp.asarray(feats),
             "edge_src": jnp.asarray(a, jnp.int32),
             "edge_dst": jnp.asarray(b, jnp.int32),
             "edge_mask": jnp.ones(len(a)), "node_mask": jnp.ones(n),
             "labels": jnp.asarray(deg > np.median(deg), jnp.int32),
             "label_mask": jnp.ones(n)}
    opt = adamw.init(params)
    ocfg = adamw.AdamWConfig(lr=1e-2, warmup_steps=2, total_steps=30)

    @jax.jit
    def step(p, o):
        loss, g = jax.value_and_grad(
            lambda p: G.loss_fn(cfg, p, batch)[0])(p)
        p, o, _ = adamw.apply(ocfg, p, g, o)
        return p, o, loss

    losses = []
    for i in range(30):
        params, opt, loss = step(params, opt)
        losses.append(float(loss))
    print(f"[gnn]    GCN on census features: loss {losses[0]:.3f} -> "
          f"{losses[-1]:.3f} over 30 steps")
    print(f"[done]   total {time.time()-t0:.1f}s")


if __name__ == "__main__":
    main()
