#!/usr/bin/env python3
"""Run the out-of-core triangle engine end to end on a TPU and check it.

    python chip_smoke.py              # one chip: every phase below
    python chip_smoke.py --chips 4    # four chips: sharded count + Fabric

One chip. A Graph500 graph (Kronecker A/B/C = 0.57/0.19/0.19, edge factor
16, labels permuted from ``--seed``) is ingested through
``TriangleEngine.ingest`` into an on-disk, degree-oriented edge store. An
exact triangle count is computed with scipy, independently of the engine.
Then each phase runs through a user's entry point and must equal it:
``TriangleEngine`` counts (``auto`` under both planners, forced
``pallas``), the full ``TriangleEngine.list()`` and a ``QueryEngine``
triangle count. A second store from the same generator at
``--small-scale`` holds boxes small enough for the forced ``dense`` and
``fused`` lanes' envelopes; there those two counts, a ``QueryEngine``
four-clique count and a ``repro.serve.Server`` answering concurrent
queries (each equal to its solo run) are checked too. A forced phase must
place at least one box on its own lane.

Four chips. Only the box-sharded ``TriangleEngine`` count over every
device and ``Fabric`` with a four-device mesh reduction, each checked
against the reference and a one-chip count.

Every count is exact or the run fails. Lines before the last are
diagnostics (wall times end in a host int); the last line is the JSON
device record. The run refuses to start without a TPU.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Callable, Dict, Iterator, Optional, Tuple

import numpy as np

ROOT = Path(__file__).resolve().parent
MIN_BOXES = 16          # every store's plan has at least this many boxes
LANE_FIELDS = ("n_dense_boxes", "n_pallas_boxes", "n_fused_boxes",
               "n_binary_boxes", "n_host_boxes", "n_kernel_boxes",
               "n_device_join_boxes", "n_dense_fallbacks",
               "n_fused_fallbacks", "n_padded_fallbacks",
               "n_kernel_fallbacks", "n_device_join_fallbacks")


class SmokeFailure(AssertionError):
    """A phase disagreed with its reference or missed its lane."""


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


_T0 = time.perf_counter()


def log(msg: str) -> None:
    print(f"[{time.perf_counter() - _T0:8.1f}s] {msg}", flush=True)


# ---------------------------------------------------------------------------
# references: scipy over the raw edges, independent of the engine
# ---------------------------------------------------------------------------

def reference_dag(src: np.ndarray, dst: np.ndarray):
    """Simple undirected graph as a scipy CSR DAG, each edge pointing from
    its lower-degree end (ties by id)."""
    import scipy.sparse as sp

    n = int(max(src.max(initial=0), dst.max(initial=0))) + 1
    keep = src != dst
    lo = np.minimum(src[keep], dst[keep])
    hi = np.maximum(src[keep], dst[keep])
    key = np.unique(lo * n + hi)
    a, b = key // n, key % n
    deg = np.bincount(a, minlength=n) + np.bincount(b, minlength=n)
    flip = (deg[a] > deg[b]) | ((deg[a] == deg[b]) & (a > b))
    u, v = np.where(flip, b, a), np.where(flip, a, b)
    m = sp.csr_matrix((np.ones(len(u), np.int32), (u, v)), shape=(n, n))
    m.sort_indices()
    return m


def reference_triangles(dag) -> int:
    """Σ over DAG edges (u, v) of |N+(u) ∩ N+(v)|, in row blocks."""
    total, n, step = 0, dag.shape[0], 1 << 16
    for lo in range(0, n, step):
        blk = dag[lo:lo + step]
        total += int((blk @ dag).multiply(blk).sum(dtype=np.int64))
    return total


def reference_four_cliques(dag) -> int:
    """Σ over vertices u of the triangles among N+(u)."""
    total = 0
    ip, ix = dag.indptr, dag.indices
    for u in np.flatnonzero(np.diff(ip) >= 3):
        nb = ix[ip[u]:ip[u + 1]]
        sub = dag[nb][:, nb]
        if sub.nnz >= 3:
            total += int((sub @ sub).multiply(sub).sum(dtype=np.int64))
    return total


def canonical_keys(rows: np.ndarray, n: int) -> np.ndarray:
    """One int64 per row: its vertex ids sorted, packed base ``n``."""
    r = np.sort(np.asarray(rows, dtype=np.int64), axis=1)
    key = r[:, 0]
    for c in range(1, r.shape[1]):
        key = key * n + r[:, c]
    return key


def check_triangle_rows(rows: np.ndarray, dag, want: int, what: str, *,
                        canonical: bool = False, step: int = 1 << 24) -> None:
    """``rows`` lists every triangle once: ``want`` rows, no two the same
    vertex set, and all three pairs of every row are graph edges.

    ``canonical=True`` holds the rows to ``TriangleEngine.list()``'s
    contract — each row ascending, rows in strictly ascending order —
    which also proves them unique without a sort. Rows are checked in
    ``step``-row chunks to bound host memory."""
    n = dag.shape[0]
    check(rows.shape == (want, 3),
          f"{what}: {rows.shape} rows, reference {want} triangles")
    coo = dag.tocoo()
    edges = np.sort(np.minimum(coo.row, coo.col).astype(np.int64) * n
                    + np.maximum(coo.row, coo.col))
    keys = np.empty(len(rows), np.int64)
    for s in range(0, len(rows), step):
        r = np.asarray(rows[s:s + step], dtype=np.int64)
        if canonical:
            check(bool(np.all((r[:, 0] < r[:, 1]) & (r[:, 1] < r[:, 2]))),
                  f"{what}: a row is not in ascending order")
        keys[s:s + step] = canonical_keys(r, n)
        for i, j in ((0, 1), (0, 2), (1, 2)):
            q = np.minimum(r[:, i], r[:, j]) * n + np.maximum(r[:, i],
                                                              r[:, j])
            pos = np.minimum(np.searchsorted(edges, q), len(edges) - 1)
            check(bool(np.all(edges[pos] == q)),
                  f"{what}: a row's pair ({i},{j}) is not an edge")
    if canonical:
        check(bool(np.all(keys[1:] > keys[:-1])),
              f"{what}: rows not strictly ascending (duplicate or order)")
    else:
        keys.sort()
        check(bool(np.all(keys[1:] != keys[:-1])), f"{what}: duplicate rows")


# ---------------------------------------------------------------------------
# measurement helpers (diagnostics, not benchmark numbers)
# ---------------------------------------------------------------------------

def timed(fn: Callable):
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


def lane_mix(stats) -> Dict[str, int]:
    return {f: getattr(stats, f) for f in LANE_FIELDS
            if getattr(stats, f, 0)}


def peak_bytes(devices) -> list:
    out = []
    for d in devices:
        ms = d.memory_stats() or {}
        out.append(ms.get("peak_bytes_in_use"))
    return out


def resolved_kernels(obj, kernels: bool) -> None:
    """On the chip every engine must have resolved to compiled kernels."""
    flag = obj.use_pallas_kernels
    check(bool(flag) == kernels,
          f"{type(obj).__name__}: use_pallas_kernels={flag}, "
          f"expected {kernels}")


def _kernel_arg(kernels: bool) -> Optional[bool]:
    # None lets each entry point resolve it from the platform, which is
    # what the chip run must exercise; False pins interpret mode
    return None if kernels else False


# ---------------------------------------------------------------------------
# set-up
# ---------------------------------------------------------------------------

def build_store(path: Path, scale: int, seed: int, kernels: bool):
    """Graph500 edges -> degree-oriented edge store via the streaming
    ingest. Returns (engine over the store, src, dst)."""
    from repro.core.engine import TriangleEngine
    from repro.data.graphs import graph500_edges

    src, dst = graph500_edges(scale, seed=seed)
    eng = TriangleEngine.ingest(
        path, (src, dst), orientation="degree",
        ingest_budget_words=1 << 24, chunk_rows=4096,
        use_pallas_kernels=_kernel_arg(kernels))
    return eng, src, dst


def pick_mem_words(store: Path) -> Tuple[int, int]:
    """Largest power-of-two budget whose uniform plan has at least
    ``MIN_BOXES`` boxes."""
    from repro.core.engine import TriangleEngine

    eng = TriangleEngine(store=store, use_pallas_kernels=False)
    words = 1 << int(np.ceil(np.log2(max(2, eng.source.n_edges))))
    while words >= 64:
        eng.mem_words = words
        n = len(eng.plan())
        if n >= MIN_BOXES:
            return words, n
        words //= 2
    raise SmokeFailure(f"no budget gives {MIN_BOXES} boxes")


# ---------------------------------------------------------------------------
# one-chip phases; each returns its diagnostics and raises on a mismatch
# ---------------------------------------------------------------------------

def phase_count(store, want: int, mem_words: int, *, kernels: bool,
                backend: str = "auto", skew: str = "uniform",
                own_lane: Optional[str] = None, warm: bool = True) -> dict:
    """TriangleEngine.count() cold (compiles included) and warm."""
    from repro.core.engine import TriangleEngine

    eng = TriangleEngine(store=store, mem_words=mem_words, backend=backend,
                         skew=skew, shard=False,
                         use_pallas_kernels=_kernel_arg(kernels))
    resolved_kernels(eng, kernels)
    got, cold = timed(eng.count)
    check(got == want, f"count {backend}/{skew}: {got} != reference {want}")
    out = {"count": got, "boxes": eng.stats.n_boxes, "cold_s": cold,
           "lanes": lane_mix(eng.stats),
           "device_invocations": eng.stats.device_invocations}
    if own_lane is not None:
        check(getattr(eng.stats, f"n_{own_lane}_boxes") > 0,
              f"count {backend}: no box ran on the {own_lane} lane "
              f"({out['lanes']})")
    if warm:
        again, out["warm_s"] = timed(eng.count)
        check(again == want, f"count {backend}/{skew} warm: {again}")
    return out


def phase_list(store, dag, want: int, mem_words: int, *,
               kernels: bool) -> dict:
    """Full TriangleEngine.list(): every triangle exactly once."""
    from repro.core.engine import TriangleEngine

    eng = TriangleEngine(store=store, mem_words=mem_words, shard=False,
                         use_pallas_kernels=_kernel_arg(kernels))
    resolved_kernels(eng, kernels)
    rows, cold = timed(eng.list)
    check_triangle_rows(rows, dag, want, "TriangleEngine.list",
                        canonical=True)
    again, warm = timed(eng.list)
    check(np.array_equal(again, rows), "TriangleEngine.list warm differs")
    return {"rows": len(rows), "cold_s": cold, "warm_s": warm,
            "rescans": eng.stats.n_rescans}


def phase_query(store, query_name: str, want: int, mem_words: int, *,
                kernels: bool, warm: bool = True) -> dict:
    """QueryEngine count of a pattern over the store."""
    from repro.query import QueryEngine
    from repro.query.patterns import PATTERNS

    qe = QueryEngine(PATTERNS[query_name](), store=store,
                     mem_words=mem_words,
                     use_pallas_kernels=_kernel_arg(kernels))
    resolved_kernels(qe, kernels)
    got, cold = timed(qe.count)
    check(got == want, f"QueryEngine {query_name}: {got} != {want}")
    out = {"count": got, "boxes": qe.stats.n_boxes, "cold_s": cold,
           "lanes": lane_mix(qe.stats),
           "device_invocations": qe.stats.device_invocations}
    if warm:
        again, out["warm_s"] = timed(qe.count)
        check(again == want, f"QueryEngine {query_name} warm: {again}")
    return out


def phase_server(store, dag, want_tri: int, want_k4: int, mem_words: int,
                 *, kernels: bool) -> dict:
    """A Server answering three concurrent queries, each equal to its
    solo run and to the reference."""
    from repro.serve import Server

    with Server({"E": str(store)}, mem_words=mem_words,
                use_pallas_kernels=_kernel_arg(kernels)) as srv:
        resolved_kernels(srv, kernels)
        t0 = time.perf_counter()
        h_tri = srv.submit("triangle", "count")
        h_k4 = srv.submit("four_clique", "count")
        h_list = srv.submit("triangle", "list", stream=True)
        pages = list(h_list.pages())
        got = {"triangle": h_tri.result(), "four_clique": h_k4.result()}
        wall = time.perf_counter() - t0
        rows = np.concatenate(pages) if pages \
            else np.zeros((0, 3), np.int64)
        check(got["triangle"] == want_tri,
              f"Server triangle {got['triangle']} != {want_tri}")
        check(got["four_clique"] == want_k4,
              f"Server four_clique {got['four_clique']} != {want_k4}")
        check_triangle_rows(rows, dag, want_tri, "Server paged list")
        for h, q in ((h_tri, "triangle"), (h_k4, "four_clique")):
            solo, _ = srv.solo_run(q, "count", words=h.admitted_words)
            check(solo == got[q], f"Server {q} {got[q]} != solo {solo}")
        solo_rows, _ = srv.solo_run("triangle", "list",
                                    words=h_list.admitted_words)
        n = dag.shape[0]
        check(np.array_equal(np.sort(canonical_keys(rows, n)),
                             np.sort(canonical_keys(solo_rows, n))),
              "Server paged list differs from its solo run")
    return {"wall_s": wall, "pages": len(pages), "rows": len(rows),
            "admitted_words": [h.admitted_words
                               for h in (h_tri, h_k4, h_list)]}


# ---------------------------------------------------------------------------
# four-chip phases
# ---------------------------------------------------------------------------

def phase_sharded(store, want: int, mem_words: int, devices, *,
                  kernels: bool) -> dict:
    """Box-sharded TriangleEngine count over all devices vs one chip."""
    import warnings

    from repro.core.engine import TriangleEngine

    one = TriangleEngine(store=store, mem_words=mem_words,
                         devices=devices[:1], shard=False,
                         use_pallas_kernels=_kernel_arg(kernels))
    single, t_one = timed(one.count)
    check(single == want, f"one-chip count {single} != reference {want}")
    with warnings.catch_warnings():
        # staging through host memory is the sharded path's contract
        warnings.simplefilter("ignore", UserWarning)
        eng = TriangleEngine(store=store, mem_words=mem_words,
                             devices=devices, shard=True,
                             use_pallas_kernels=_kernel_arg(kernels))
    resolved_kernels(eng, kernels)
    got, cold = timed(eng.count)
    check(got == want, f"sharded count {got} != reference {want}")
    again, warm = timed(eng.count)
    check(again == want, f"sharded count warm {again}")
    return {"count": got, "one_chip": single, "one_chip_s": t_one,
            "cold_s": cold, "warm_s": warm,
            "shard_edges": eng.stats.shard_edges}


def phase_fabric(src, dst, want: int, mem_words: int, n_shards: int, *,
                 kernels: bool) -> dict:
    """Fabric triangle count with the psum over an n-device mesh."""
    from repro.launch.mesh import fabric_mesh
    from repro.parallel.fabric import Fabric
    from repro.query.patterns import triangle

    fab = Fabric.from_graph(triangle(), src, dst, orientation="degree",
                            mesh=fabric_mesh(n_shards), mem_words=mem_words,
                            use_pallas_kernels=_kernel_arg(kernels))
    resolved_kernels(fab.planner, kernels)
    got, cold = timed(fab.count)
    check(got == want, f"Fabric mesh count {got} != reference {want}")
    host = sum(int(r) for rep in fab.reports for r in rep.results
               if r is not None)
    check(host == want, f"Fabric host sum of partials {host} != {want}")
    return {"count": got, "cold_s": cold,
            "shard_mass": list(fab.stats.shard_mass)}


# ---------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------

def require_tpu(chips: int):
    """The chip's devices, or exit before any phase runs."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU (JAX sees "
                         f"{devices[0].platform}); nothing was run")
    if len(devices) < chips:
        raise SystemExit(f"chip_smoke: {chips} chips asked for, "
                         f"{len(devices)} present")
    return devices


def report(name: str, res: dict, devices) -> None:
    log(f"phase {name}: {json.dumps(res)} "
        f"peak_bytes={peak_bytes(devices[:1])}")


def run_one_chip(args, workdir: Path, devices, kernels: bool) -> None:
    store = workdir / "g.store"
    (eng, src, dst), t = timed(lambda: build_store(
        store, args.scale, args.seed, kernels))
    log(f"store: scale={args.scale} generated_edges={len(src)} "
        f"stored_edges={eng.source.n_edges} vertices={eng.nv} "
        f"build_s={t:.3f}")
    dag, t_dag = timed(lambda: reference_dag(src, dst))
    want, t_ref = timed(lambda: reference_triangles(dag))
    log(f"reference: triangles={want} scipy_s={t_ref:.3f} "
        f"dag_s={t_dag:.3f}")
    del eng, src, dst
    mem_words, n_boxes = pick_mem_words(store)
    log(f"plan: mem_words={mem_words} boxes={n_boxes}")
    for name, kw in (("count auto", {}),
                     ("count auto heavy_light", dict(skew="heavy_light")),
                     ("count pallas", dict(backend="pallas",
                                           own_lane="pallas"))):
        report(name, phase_count(store, want, mem_words, kernels=kernels,
                                 **kw), devices)
    report("list", phase_list(store, dag, want, mem_words, kernels=kernels),
           devices)
    del dag
    report("query triangle", phase_query(store, "triangle", want, mem_words,
                                         kernels=kernels), devices)

    # the small store: same generator at --small-scale. The dense one-hot
    # cap and the fused kernel's VMEM admit only boxes this small, and
    # the server's solo reruns fit the run's time limit. The four-clique
    # counts (query phase and server) run on the device join lane; the
    # query phase's lane mix names n_device_join_boxes and
    # n_device_join_fallbacks
    small = workdir / "small.store"
    (_, s2, d2), t = timed(lambda: build_store(small, args.small_scale,
                                               args.seed, kernels))
    dag2 = reference_dag(s2, d2)
    tri2 = reference_triangles(dag2)
    k4, t_k4 = timed(lambda: reference_four_cliques(dag2))
    mw2, nb2 = pick_mem_words(small)
    log(f"small store: scale={args.small_scale} triangles={tri2} "
        f"four_cliques={k4} four_clique_scipy_s={t_k4:.3f} "
        f"build_s={t:.3f} mem_words={mw2} boxes={nb2}")
    for lane in ("dense", "fused"):
        report(f"count {lane} (small)",
               phase_count(small, tri2, mw2, kernels=kernels, backend=lane,
                           own_lane=lane), devices)
    report("query four_clique (small)",
           phase_query(small, "four_clique", k4, mw2, kernels=kernels),
           devices)
    report("server (small)",
           phase_server(small, dag2, tri2, k4, max(4 * mw2, 1 << 12),
                        kernels=kernels), devices)


def run_four_chips(args, workdir: Path, devices, kernels: bool) -> None:
    store = workdir / "g.store"
    (eng, src, dst), t = timed(lambda: build_store(
        store, args.scale, args.seed, kernels))
    log(f"store: scale={args.scale} stored_edges={eng.source.n_edges} "
        f"build_s={t:.3f}")
    want = reference_triangles(reference_dag(src, dst))
    log(f"reference: triangles={want}")
    mem_words, n_boxes = pick_mem_words(store)
    log(f"plan: mem_words={mem_words} boxes={n_boxes}")
    res = phase_sharded(store, want, mem_words, devices, kernels=kernels)
    log(f"phase sharded: {json.dumps(res)}")
    res = phase_fabric(src, dst, want, mem_words, len(devices),
                       kernels=kernels)
    log(f"phase fabric: {json.dumps(res)}")
    peaks = peak_bytes(devices)
    log(f"peak_bytes per device: {peaks}")
    check(all(p for p in peaks), f"a device held no memory: {peaks}")


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--scale", type=int, default=18,
                    help="Graph500 scale of the main store")
    ap.add_argument("--small-scale", type=int, default=14,
                    help="Graph500 scale of the small store (forced "
                         "dense/fused lanes, four-clique, server)")
    ap.add_argument("--workdir", default=str(ROOT / ".chip_smoke"),
                    help="where the edge stores are written, in a fresh "
                         "subdirectory removed at the end")
    return ap.parse_args(argv)


@contextlib.contextmanager
def run_dir(workdir: Path) -> Iterator[Path]:
    """A fresh subdirectory of ``workdir`` for this run's stores; only
    it is removed afterwards, never what ``workdir`` held before."""
    workdir.mkdir(parents=True, exist_ok=True)
    d = Path(tempfile.mkdtemp(prefix="run-", dir=workdir))
    try:
        yield d
    finally:
        shutil.rmtree(d, ignore_errors=True)


def main(argv=None) -> int:
    args = parse_args(argv)
    try:
        sys.path.insert(0, str(ROOT / "src"))
        import repro  # noqa: F401
    except ImportError:
        print("chip_smoke: the repro package is not beside this script",
              file=sys.stderr)
        return 2
    devices = require_tpu(args.chips)
    from repro.launch.compile_cache import enable_compile_cache

    log(f"devices: {len(devices)} x {devices[0].device_kind}; compile "
        f"cache: {enable_compile_cache()}")
    t0 = time.perf_counter()
    with run_dir(Path(args.workdir)) as d:
        if args.chips == 4:
            run_four_chips(args, d, devices[:4], kernels=True)
        else:
            run_one_chip(args, d, devices, kernels=True)
    log(f"total_s={time.perf_counter() - t0:.3f}")
    d = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": args.chips}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
