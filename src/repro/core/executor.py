"""Streaming box executor: out-of-core per-box slice pipeline.

The planner half of the engine (``core.engine.TriangleEngine``) produces a
box plan; this module executes it as a stream. For each box (lx,hx,ly,hy)
the executor

  1. pulls the box from the work queue,
  2. *materializes* a vertex-renumbered, compacted neighbor slice: only the
     rows referenced by in-box edges, padded to the box-local max degree —
     never the global (V, K) ``npad`` matrix (the paper's "feed input data
     to LFTJ" boxing idea applied at the storage layer),
  3. dispatches the slice to a backend (binary-search scan, dense MXU
     formulation, or the Pallas intersect kernel) chosen by the planner's
     density rule.

Slices are built host-side from an EdgeSource (``data.edgestore.EdgeStore``
on disk, or ``InMemoryEdgeSource``); construction overlaps device compute.
Every source read is charged to the attached ``core.iomodel.BlockDevice``,
giving measured block I/Os per run.

Two execution modes share the per-box machinery:

* ``workers=1`` (the sequential oracle): the box stream runs through a
  single ``data.pipeline.Prefetcher`` — one box in flight, host DMA of the
  next box overlapping device compute of the current one. This is the
  seed behavior every parallel configuration is pinned to.
* ``workers>1`` (async scheduler): a bounded pool of worker threads drains
  a shared work queue. The queue is ordered LPT-first
  (``repro.parallel.sharding.lpt_order`` — the same priority order the
  shard_map schedule uses), so the long-pole box starts first; an idle
  worker "steals" the next-heaviest box by popping the shared queue. Slice
  *builds* are serialized in queue order behind an in-flight (boxes, words)
  budget — the source read stream is therefore identical to a serial walk
  of the same order, which is what makes the I/O ledger (and the
  ``SliceCache`` hit pattern, which folds the queue back to plan order)
  byte-comparable to the ``workers=1`` run. Backend compute runs in
  parallel across workers, and results are reduced in *fixed box order*
  (never arrival order): counts sum and listings concatenate exactly as
  the sequential oracle would.

Peak host memory is bounded by the in-flight window: at most
``inflight_boxes`` materialized slices resident at once, their raw words
capped at ``inflight_words`` (the engine sizes this window from its memory
budget); a single slice's raw words are bounded by the planner's budget
(plus pinned-row spill boxes), which is the Thm. 10 working-set guarantee.

Device shapes are bucketed (rows, widths and edge counts to powers of two)
so the number of distinct jit traces stays logarithmic in the graph size
instead of linear in the box count: on a TPU each trace is a compile of
seconds to a minute.
"""

from __future__ import annotations

import inspect
import threading
import time
from collections import OrderedDict
from dataclasses import dataclass
from typing import Callable, Iterator, List, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from repro.data.pipeline import Prefetcher
from repro.kernels import ledger as kernel_ledger
from repro.kernels.intersect.ops import MAX_K as INTERSECT_MAX_K
from repro.obs import metrics as obs_metrics
from repro.obs.trace import maybe_span, wrap_iter, wrap_stage

from .lftj_jax import (SENTINEL, _count_chunked, _count_rows_chunked,
                       _list_hits, pad_neighbors_binned)

_ROW_BUCKET = 64
# largest padded (rows × K) slice the binary lane ships to the device
# (512 MiB of int32): a hub row of a minmax-oriented store pads every row
# of its box to the hub's width
_PADDED_WORDS_CAP = 1 << 27


class BoxQueueCancelled(RuntimeError):
    """Raised by ``run_box_queue`` when its ``cancel`` event fires before
    the queue drains: remaining boxes are abandoned, in-progress stages
    finish, every worker is joined. Boxes are idempotent, so a cancelled
    queue can simply be re-run (the serving layer's cancellation path)."""


def _pow2(n: int, lo: int = 1) -> int:
    return max(lo, 1 << int(np.ceil(np.log2(max(1, n)))))


@dataclass
class BoxSlice:
    """One box's renumbered, compacted work item.

    ``rows`` maps local row id -> global vertex id (sorted);
    ``row_off``/``row_vals`` are the slice's compact CSR form (offsets +
    concatenated sorted neighbor values per local row); ``eu``/``ev`` are
    *local* row ids of the in-box edges. ``words_read`` counts raw CSR
    words DMA'd from the source.

    ``npad`` — the (R, K) box-local padded neighbor matrix with one
    all-SENTINEL pad row at index ``len(rows)`` — is built lazily on first
    access and cached: the jax lanes need it, but the host backend probes
    the CSR form directly, so a host-lane run never pays the padded
    memset/scatter (the padded write traffic, not the probe math, is what
    limits worker-thread scaling on bandwidth-starved CPU hosts).
    """

    box: Tuple[int, int, int, int]
    rows: np.ndarray
    eu: np.ndarray
    ev: np.ndarray
    n_edges: int
    wx: int
    wy: int
    words_read: int
    row_off: np.ndarray
    row_vals: np.ndarray
    pad_shape: Tuple[int, int]
    _npad: Optional[np.ndarray] = None

    @property
    def npad(self) -> np.ndarray:
        if self._npad is None:
            n_rows, k = self.pad_shape
            npad = np.full((n_rows, k), SENTINEL, dtype=np.int32)
            deg = np.diff(self.row_off)
            if deg.sum() > 0:
                rr = np.repeat(np.arange(len(deg)), deg)
                cc = np.arange(int(deg.sum())) \
                    - np.repeat(self.row_off[:-1], deg)
                npad[rr, cc] = self.row_vals
            self._npad = npad
        return self._npad

    @property
    def padded_words(self) -> int:
        return int(self.pad_shape[0] * self.pad_shape[1])


def _gather_rows(rows: np.ndarray, slabs: list) -> Tuple[np.ndarray, np.ndarray]:
    """(deg, concat values) for sorted global ``rows`` out of range slabs.

    ``slabs`` is [(lo, hi, indptr_local, values)] with disjoint row ranges
    covering every requested row.
    """
    deg = np.zeros(len(rows), dtype=np.int64)
    starts = np.zeros(len(rows), dtype=np.int64)
    slab_of = np.full(len(rows), -1, dtype=np.int64)
    for si, (lo, hi, ip, _vals) in enumerate(slabs):
        m = (rows >= lo) & (rows <= hi)
        if not m.any():
            continue
        r = rows[m] - lo
        starts[m] = ip[r]
        deg[m] = ip[r + 1] - ip[r]
        slab_of[m] = si
    parts = []
    for si, (_lo, _hi, _ip, vals) in enumerate(slabs):
        m = slab_of == si
        if not m.any():
            continue
        s, d = starts[m], deg[m]
        total = int(d.sum())
        if total == 0:
            continue
        idx = np.repeat(s, d) + np.arange(total) \
            - np.repeat(np.cumsum(d) - d, d)
        parts.append((np.flatnonzero(m), vals[idx], d))
    # reassemble in row order (one vectorized scatter per slab)
    out = np.zeros(int(deg.sum()), dtype=np.int32)
    offs = np.concatenate([[0], np.cumsum(deg)])
    for where, vals, d in parts:
        tgt = np.repeat(offs[where], d) + np.arange(int(d.sum())) \
            - np.repeat(np.cumsum(d) - d, d)
        out[tgt] = vals
    return deg, out


class SliceCache:
    """LRU cache of row-range slices over an EdgeSource, budgeted in words.

    The box plan walks a grid: every box in one x-stripe re-reads the same
    x-slab, and boxes in adjacent x-stripes re-read the same y-slices. The
    cache exploits that locality *above* the ``iomodel.BlockDevice``. A
    ``read_rows(lo, hi)`` request is decomposed against row blocks of
    ``block_rows`` rows (aligned, sized to ~1/16 of the budget by default):

    * **interior blocks** (fully inside the request) are the cacheable
      unit. Cached ones are served from host memory — no source read, no
      block I/O charged, which is how hits visibly reduce
      ``EngineStats.block_reads``. Runs of consecutive *missing* interior
      blocks are fetched with ONE source read (the cold path keeps the
      sequential DMA pattern of the uncached engine) and split into
      per-block entries.
    * **partial edge blocks** pass straight through to the source, trimmed
      to the request. The cache therefore never reads a word the uncached
      engine would not have read — worst case (zero reuse) costs the same
      I/O, never more.

    Eviction is LRU past ``budget_words`` (raw CSR words: values + one
    indptr word per row); a single block wider than the whole budget is
    still cached alone (the pinned-row analogue at the cache layer). Words
    served by hits are also recorded on the attached device
    (``IOStats.cache_served_words``) so the modeled I/O ledger shows where
    the avoided traffic went.

    Exposes the EdgeSource interface; everything else (``n_nodes``,
    ``indptr``, ``degrees``, ...) proxies to the wrapped source.
    ``read_rows`` serializes on an internal lock, so the cache ledger (LRU
    order, word totals, hit counters) stays consistent when the async box
    scheduler's workers share one cache; the scheduler additionally
    serializes slice *builds* in plan order whenever a cache is attached,
    so the hit/miss sequence — not just the totals — matches the serial
    run's.
    """

    def __init__(self, source, budget_words: int,
                 block_rows: Optional[int] = None,
                 tracer=None):
        self.source = source
        # obs.trace.Tracer emitting cache.hit/miss/evict instant events;
        # None (default) keeps the hot path at one attribute check
        self.tracer = tracer
        self._lock = threading.RLock()
        self.budget_words = max(1, int(budget_words))
        if block_rows is None:
            # fine granularity maximizes interior coverage of the planner's
            # small y-segment reads (hits only happen on fully-covered
            # blocks); ~32 words per block measured best across budgets.
            # The budget/4096 floor bounds the entry count so a huge cache
            # doesn't drown in per-block bookkeeping.
            chunk = int(getattr(source, "chunk_rows", 256))
            avg = source.n_edges / max(1, source.n_nodes) + 2.0
            target = max(32, self.budget_words // 4096)
            block_rows = int(min(chunk, max(2.0, round(target / avg))))
        self.block_rows = max(1, int(block_rows))
        self._blocks: OrderedDict = OrderedDict()  # block id -> (ip, vals)
        self._words = 0
        self.hits = 0
        self.misses = 0
        self.hit_words = 0      # words served from cache
        self.miss_words = 0     # words read from the source into the cache
        self.passthrough_words = 0   # partial-edge words (never cached)

    # -- EdgeSource interface ------------------------------------------------

    def __getattr__(self, name):
        return getattr(self.source, name)

    def _read_through(self, lo: int, hi: int):
        """Uncached trimmed read (partial edge blocks)."""
        ip, vals = self.source.read_rows(lo, hi)
        self.passthrough_words += len(vals)
        return ip, vals

    def _hit(self, bid: int, ent) -> None:
        """Bookkeeping hook for one block served from cache (subclasses —
        the serving layer's multi-tenant cache — attribute per tenant)."""
        self.hits += 1
        self.hit_words += len(ent[1])
        tr = self.tracer
        if tr is not None:
            tr.event("cache.hit", block=bid, words=len(ent[1]))

    def _miss(self, n_blocks: int, n_words: int) -> None:
        """Bookkeeping hook for a missing-block run read from the source."""
        self.misses += n_blocks
        self.miss_words += n_words
        tr = self.tracer
        if tr is not None:
            tr.event("cache.miss", blocks=n_blocks, words=n_words)

    def _fetch_run(self, b0: int, b1: int) -> list:
        """One sequential source read covering missing blocks b0..b1, split
        into per-block cache entries. Returns the entries in block order
        (the caller assembles from them directly, so an insert-time
        eviction inside this very request never forces a re-read)."""
        br = self.block_rows
        ip, vals = self.source.read_rows(b0 * br, b1 * br + br - 1)
        self._miss(b1 - b0 + 1, len(vals))
        entries = []
        for bid in range(b0, b1 + 1):
            r0 = (bid - b0) * br
            s, e = int(ip[r0]), int(ip[r0 + br])
            ent = (np.asarray(ip[r0:r0 + br + 1] - ip[r0]),
                   np.asarray(vals[s:e]))
            self._insert(bid, ent)
            entries.append(ent)
        return entries

    def read_rows(self, lo: int, hi: int) -> Tuple[np.ndarray, np.ndarray]:
        with self._lock:
            return self._read_rows_locked(lo, hi)

    def _read_rows_locked(self, lo: int,
                          hi: int) -> Tuple[np.ndarray, np.ndarray]:
        nv = self.source.n_nodes
        lo = max(0, int(lo))
        hi = min(nv - 1, int(hi))
        if hi < lo:
            return np.zeros(1, np.int64), np.zeros(0, np.int32)
        br = self.block_rows
        # interior = the aligned blocks fully covered by [lo, hi]
        ib0 = -(-lo // br)                   # first block starting >= lo
        ib1 = (hi + 1) // br - 1             # last block ending <= hi
        if ib1 < ib0:
            return self._read_through(lo, hi)
        dev = getattr(self.source, "device", None)
        parts = []                            # (ip_local, vals) in row order
        if lo < ib0 * br:
            parts.append(self._read_through(lo, ib0 * br - 1))
        bid = ib0
        while bid <= ib1:
            ent = self._blocks.get(bid)
            if ent is not None:
                self._blocks.move_to_end(bid)
                self._hit(bid, ent)
                if dev is not None:
                    dev.serve_from_cache(len(ent[1]))
                parts.append(ent)
                bid += 1
            else:
                run_end = bid
                while run_end + 1 <= ib1 \
                        and run_end + 1 not in self._blocks:
                    run_end += 1
                parts.extend(self._fetch_run(bid, run_end))
                bid = run_end + 1
        if hi >= (ib1 + 1) * br:
            parts.append(self._read_through((ib1 + 1) * br, hi))
        if len(parts) == 1:
            return parts[0]
        deg = np.concatenate([np.diff(p[0]) for p in parts])
        ip_out = np.concatenate([np.zeros(1, np.int64),
                                 np.cumsum(deg, dtype=np.int64)])
        return ip_out, np.concatenate([p[1] for p in parts])

    # -- LRU bookkeeping -----------------------------------------------------

    @staticmethod
    def _entry_words(ent) -> int:
        return len(ent[1]) + len(ent[0])

    def _insert(self, bid: int, ent) -> None:
        self._blocks[bid] = ent
        self._words += self._entry_words(ent)
        tr = self.tracer
        while self._words > self.budget_words and len(self._blocks) > 1:
            old_bid, old = self._blocks.popitem(last=False)
            self._words -= self._entry_words(old)
            if tr is not None:
                tr.event("cache.evict", block=old_bid,
                         words=self._entry_words(old))

    def clear(self) -> None:
        with self._lock:
            self._blocks.clear()
            self._words = 0

    @property
    def hit_rate(self) -> float:
        total = self.hits + self.misses
        return self.hits / total if total else 0.0


def run_box_serial(items: List, *,
                   fetch: Callable[[object], Tuple[object, int]],
                   build: Callable[[object], object],
                   work: Callable[[object], object],
                   prefetch_depth: int = 2,
                   cancel: Optional[threading.Event] = None,
                   tracer=None) -> List:
    """The ``workers=1`` oracle drain: one ``Prefetcher`` pipeline (fetch
    + build of the next item overlap the current item's ``work``), items
    strictly in list order, per-item results in list order (``None`` for
    skipped items). This is the serial counterpart of ``run_box_queue``
    and the reference every ledger contract in the repo is pinned against
    — the generic ``QueryEngine`` delegates its serial path here, and
    ``parallel.fabric`` re-runs any shard's restricted plan through it to
    reproduce the shard's device ledger byte for byte. ``cancel`` aborts
    with ``BoxQueueCancelled`` exactly like the pooled scheduler.
    ``tracer`` wraps each stage in ``box.fetch``/``box.build``/
    ``box.compute`` spans (``obs.trace``); tracing is read-only — stage
    order, prefetch depth and every ledger are untouched."""
    fetch = wrap_stage(tracer, "box.fetch", fetch)
    build = wrap_stage(tracer, "box.build", build)
    work = wrap_stage(tracer, "box.compute", work)
    results: List = [None] * len(items)
    pf = Prefetcher((build(fetch(it)[0]) for it in items),
                    depth=max(1, int(prefetch_depth)))
    try:
        for i, built in enumerate(pf):
            if cancel is not None and cancel.is_set():
                raise BoxQueueCancelled(
                    "query cancelled before draining its boxes")
            if built is None:
                continue
            results[i] = work(built)
    finally:
        pf.close()
    return results


def run_box_queue(items: List, *, order: List[int],
                  est_words: Callable[[object], int],
                  fetch: Callable[[object], Tuple[object, int]],
                  build: Callable[[object], object],
                  work: Callable[[object], object],
                  workers: int,
                  inflight_items: int,
                  inflight_words: Optional[int] = None,
                  cancel: Optional[threading.Event] = None,
                  tracer=None):
    """Drain a box work queue on a bounded worker pool (the PR-4 scheduler).

    This is the shared queue machinery of every boxed executor in the repo
    (the triangle ``StreamingExecutor`` and the generic
    ``repro.query.QueryEngine``): a pool of ``workers`` threads (clamped to
    the hardware parallelism and the item count) drains ``items`` in
    ``order``, with the three per-item stages split so the determinism
    contract holds for ANY workload:

    * ``fetch(item) -> (payload, actual_words)`` — all *source reads* of
      one item. Serialized in queue order behind the in-flight
      (items, words) window, so the read stream — and every ledger derived
      from it (``BlockDevice`` I/Os, ``SliceCache`` hit sequence) — is
      identical to a serial walk of ``order``.
    * ``build(payload) -> obj | None`` — pure host-side construction (no
      source access); runs concurrently across workers. ``None`` skips the
      item (empty box).
    * ``work(obj) -> result`` — the backend; concurrent across workers.

    Admission charges ``est_words(item)`` against the window up front and
    corrects to the fetch's actual words once known; an item wider than the
    whole window is admitted alone (pinned-spill rule) so the queue cannot
    deadlock on it. A stage exception cancels the remaining queue, every
    worker is joined, and the first error re-raises here. An optional
    ``cancel`` event aborts the same way from outside: no new item is
    claimed once it is set, in-progress stages finish, workers join, and
    ``BoxQueueCancelled`` raises (unless a stage error got there first).

    Returns ``(results, telemetry)``: per-item results in *item order*
    (``None`` for skipped items) for deterministic reduction, plus the
    telemetry dict (wait/build/compute worker-seconds, in-flight peaks,
    wall time, pool size) the caller folds into its stats object.

    ``tracer`` (an ``obs.trace.Tracer``) wraps the three stages in
    ``box.fetch`` / ``box.build`` / ``box.compute`` spans — one pair per
    item per stage, emitted from the worker thread running it, so the
    exported timeline shows one lane per pool thread. Tracing is
    strictly read-only: the turnstile, the admission window and every
    derived ledger behave identically with it attached.
    """
    import os as _os

    fetch = wrap_stage(tracer, "box.fetch", fetch)
    build = wrap_stage(tracer, "box.build", build)
    work = wrap_stage(tracer, "box.compute", work)

    n = len(items)
    results: List = [None] * n
    max_boxes = max(1, int(inflight_items))
    max_words = inflight_words
    # the pool never exceeds the hardware parallelism: beyond it, extra
    # runnable threads only thrash caches and the GIL (measured
    # monotonic slowdown on 2-core hosts)
    pool = max(1, min(workers, n, _os.cpu_count() or workers))
    cond = threading.Condition()
    state = {"next": 0, "building": False, "res_boxes": 0,
             "res_words": 0, "err": None, "stop": False}
    tele = {"wait": 0.0, "build": 0.0, "compute": 0.0,
            "hi_boxes": 0, "hi_words": 0, "wall": 0.0, "pool": 0}

    def loop():
        try:
            _loop_body()
        except BaseException as e:  # noqa: BLE001 — never strand waiters
            with cond:
                if state["err"] is None:
                    state["err"] = e
                state["stop"] = True
                state["building"] = False
                cond.notify_all()

    def _loop_body():
        while True:
            t0 = time.perf_counter()
            with cond:
                while True:
                    if cancel is not None and cancel.is_set():
                        state["stop"] = True
                        cond.notify_all()
                    if state["stop"] or state["next"] >= n:
                        tele["wait"] += time.perf_counter() - t0
                        return
                    if not state["building"]:
                        est = est_words(items[order[state["next"]]])
                        fits = (state["res_boxes"] < max_boxes
                                and (max_words is None
                                     or state["res_words"] + est
                                     <= max_words))
                        # an item wider than the whole window (pinned
                        # spill row) is admitted alone, or the queue
                        # would deadlock on it
                        if fits or state["res_boxes"] == 0:
                            break
                    # poll so an externally-set cancel event is noticed even
                    # when no stage completion notifies the condition
                    cond.wait(timeout=0.05 if cancel is not None else None)
                bi = order[state["next"]]
                state["next"] += 1
                state["building"] = True
                state["res_boxes"] += 1
                state["res_words"] += est
                tele["wait"] += time.perf_counter() - t0
                tele["hi_boxes"] = max(tele["hi_boxes"],
                                       state["res_boxes"])
            actual = 0
            try:
                t1 = time.perf_counter()
                # serialized stage: only the source reads. build and work
                # run outside the turnstile, concurrently across workers.
                payload, actual = fetch(items[bi])
                with cond:
                    state["building"] = False
                    state["res_words"] += actual - est
                    tele["hi_words"] = max(tele["hi_words"],
                                           state["res_words"])
                    cond.notify_all()
                obj = build(payload)
                t3 = time.perf_counter()
                with cond:
                    tele["build"] += t3 - t1
                if obj is not None:
                    out = work(obj)
                    with cond:
                        tele["compute"] += time.perf_counter() - t3
                    results[bi] = out
                with cond:
                    state["res_boxes"] -= 1
                    state["res_words"] -= actual
                    cond.notify_all()
            except BaseException as e:  # noqa: BLE001
                with cond:
                    if state["err"] is None:
                        state["err"] = e
                    state["stop"] = True      # cancel remaining items
                    state["building"] = False
                    state["res_boxes"] -= 1
                    state["res_words"] -= actual
                    cond.notify_all()
                return

    t_start = time.perf_counter()
    threads = [threading.Thread(target=loop, daemon=True,
                                name=f"box-worker-{i}")
               for i in range(pool)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    tele["wall"] = time.perf_counter() - t_start
    tele["pool"] = len(threads)
    if state["err"] is not None:
        raise state["err"]
    if cancel is not None and cancel.is_set():
        raise BoxQueueCancelled("box queue cancelled before draining")
    return results, tele


def merge_queue_telemetry(stats, tele: dict, lock: threading.Lock,
                          inflight_boxes: int,
                          metrics=None, lane: str = "all") -> None:
    """Fold one ``run_box_queue`` telemetry dict into a stats object that
    carries the PR-4 scheduler fields (``EngineStats`` and
    ``repro.query.QueryStats`` both do).

    ``worker_utilization`` is ``busy / (pool * wall)``; a sub-millisecond
    run can finish with ``wall == 0.0`` (perf_counter granularity) or a
    degenerate pool, in which case the ratio is undefined — it is
    reported as ``None``, never a garbage division.

    ``metrics`` (an ``obs.metrics.MetricsRegistry``) additionally folds
    the telemetry into the ``box.*{lane=...}`` series; the process-wide
    default registry (benchmark harness opt-in) is used when none is
    passed.
    """
    busy = tele["build"] + tele["compute"]
    wall = tele["wall"]
    with lock:
        stats.n_workers = tele["pool"]
        stats.inflight_boxes = inflight_boxes
        stats.queue_wait_s += tele["wait"]
        stats.build_s += tele["build"]
        stats.compute_s += tele["compute"]
        stats.overlap_s += max(0.0, busy - wall)
        stats.worker_utilization = busy / (tele["pool"] * wall) \
            if wall > 0.0 and tele["pool"] > 0 else None
        stats.max_inflight_boxes = max(stats.max_inflight_boxes,
                                       tele["hi_boxes"])
        stats.max_inflight_words = max(stats.max_inflight_words,
                                       tele["hi_words"])
    reg = metrics if metrics is not None \
        else obs_metrics.default_registry()
    if reg is not None:
        reg.note_queue(tele, lane=lane)


class StreamingExecutor:
    """Pulls boxes from a work queue, materializes slices, runs backends.

    ``workers=1`` is the sequential oracle (single Prefetcher pipeline);
    ``workers>1`` runs the async scheduler described in the module
    docstring. ``inflight_boxes``/``inflight_words`` bound the window of
    materialized-but-unreduced slices (defaults: ``2*workers`` boxes,
    unbounded words — the engine passes a word cap derived from its memory
    budget).
    """

    def __init__(self, source, *,
                 pick_backend: Callable[[int, int, int], str],
                 chunk: int = 2048,
                 prefetch_depth: int = 2,
                 use_pallas_kernels: bool = False,
                 dense_words_cap: int = 64_000_000,
                 stats=None,
                 workers: int = 1,
                 degree_bins: bool = False,
                 inflight_boxes: Optional[int] = None,
                 inflight_words: Optional[int] = None,
                 tracer=None,
                 metrics=None):
        self.source = source
        # observability (both optional, both None by default — the traced-
        # off path is one attribute check per site): span/event recorder
        # and the cross-layer MetricsRegistry kernel/queue series feed
        self.tracer = tracer
        self.metrics = metrics
        self.pick_backend = pick_backend
        # a box-aware dispatcher (the skew-routing engine) takes the box as
        # a fourth argument; plain (n_edges, wx, wy) callables keep working
        try:
            params = inspect.signature(pick_backend).parameters.values()
            self._backend_takes_box = any(
                p.name == "box"
                or p.kind is inspect.Parameter.VAR_POSITIONAL
                for p in params)
        except (TypeError, ValueError):
            self._backend_takes_box = False
        self.degree_bins = bool(degree_bins)
        self.chunk = int(chunk)
        self.prefetch_depth = max(1, int(prefetch_depth))
        self.use_pallas_kernels = bool(use_pallas_kernels)
        self.dense_words_cap = int(dense_words_cap)
        self.stats = stats
        self.workers = max(1, int(workers))
        self.inflight_boxes = max(1, int(inflight_boxes)) \
            if inflight_boxes is not None else max(2, 2 * self.workers)
        self.inflight_words = int(inflight_words) \
            if inflight_words is not None else None
        # serializes every EngineStats mutation: workers note slices and
        # backend counters concurrently against the one shared stats object
        self._stats_lock = threading.Lock()

    # -- slice materialization (host side, overlapped via Prefetcher) --------

    def _fetch(self, box, x_slab=None):
        """All *source reads* of one box — the stage the async scheduler
        serializes in queue order, so the read stream (and every derived
        ledger: device I/Os, cache hits) is identical to a serial walk.
        ``x_slab`` is an optional pre-read ``read_rows(lx, hx)`` result so
        a caller that already extracted the box's edges (backend selection,
        shard scheduling) doesn't charge the x-range DMA twice. Returns
        ``None`` for a degenerate box, else the raw slabs + in-box edges
        for ``_compact``."""
        nv = self.source.n_nodes
        lx, hx, ly, hy = box
        lx_, hx_ = max(int(lx), 0), min(int(hx), nv - 1)
        ly_, hy_ = max(int(ly), 0), min(int(hy), nv - 1)
        if hx_ < lx_ or hy_ < ly_:
            return None
        ip_x, vx = x_slab if x_slab is not None \
            else self.source.read_rows(lx_, hx_)
        words = len(vx)
        eu_g = np.repeat(np.arange(lx_, hx_ + 1), np.diff(ip_x))
        ev_g = vx.astype(np.int64)
        sel = (ev_g >= ly_) & (ev_g <= hy_)
        eu_g, ev_g = eu_g[sel], ev_g[sel]
        slabs = [(lx_, hx_, ip_x, vx)]
        if len(eu_g):
            # provision the y slice too (E(y, z) rows); dedup the x
            # overlap (§5)
            for seg_lo, seg_hi in ((ly_, min(hy_, lx_ - 1)),
                                   (max(ly_, hx_ + 1), hy_)):
                if seg_hi >= seg_lo:
                    ip_s, vs = self.source.read_rows(seg_lo, seg_hi)
                    words += len(vs)
                    slabs.append((seg_lo, seg_hi, ip_s, vs))
        return (box, (lx_, hx_, ly_, hy_), slabs, eu_g, ev_g, words)

    def _compact(self, fetched) -> Optional[BoxSlice]:
        """Pure-numpy renumber/compact/pad of a fetched box — no source
        access, so the scheduler runs it concurrently across workers
        (numpy's sort/unique/searchsorted kernels release the GIL)."""
        if fetched is None:
            return None
        box, (lx_, hx_, ly_, hy_), slabs, eu_g, ev_g, words = fetched
        if len(eu_g) == 0:
            return BoxSlice(box, np.zeros(0, np.int64),
                            np.zeros(0, np.int32), np.zeros(0, np.int32),
                            0, hx_ - lx_ + 1, hy_ - ly_ + 1, words,
                            row_off=np.zeros(1, np.int64),
                            row_vals=np.zeros(0, np.int32),
                            pad_shape=(0, 0))
        rows = np.unique(np.concatenate([eu_g, ev_g]))
        deg, vals = _gather_rows(rows, slabs)
        k = _pow2(int(deg.max(initial=1)), lo=8)
        n_rows = _pow2(len(rows) + 1, lo=_ROW_BUCKET)
        eu = np.searchsorted(rows, eu_g).astype(np.int32)
        ev = np.searchsorted(rows, ev_g).astype(np.int32)
        off = np.concatenate([np.zeros(1, np.int64),
                              np.cumsum(deg, dtype=np.int64)])
        return BoxSlice(box, rows, eu, ev, len(eu),
                        hx_ - lx_ + 1, hy_ - ly_ + 1, words,
                        row_off=off, row_vals=vals, pad_shape=(n_rows, k))

    def _materialize(self, box, x_slab=None) -> Optional[BoxSlice]:
        """Build the box slice (fetch + compact in one step — the serial
        pipeline and one-off ``count_box`` path)."""
        return self._compact(self._fetch(box, x_slab=x_slab))

    def _stream(self, boxes) -> Iterator[Optional[BoxSlice]]:
        mat = wrap_stage(self.tracer, "box.fetch", self._materialize)
        return Prefetcher((mat(b) for b in boxes),
                          depth=self.prefetch_depth)

    def _note(self, slc: BoxSlice) -> None:
        s = self.stats
        if s is None:
            return
        with self._stats_lock:
            s.n_streamed_boxes += 1
            s.slice_words_read += slc.words_read
            s.max_slice_words = max(s.max_slice_words, slc.words_read)
            s.max_slice_padded_words = max(s.max_slice_padded_words,
                                           slc.padded_words)

    def _note_padding(self, slc: BoxSlice, extra: int = 0) -> None:
        """Charge the padded-vs-actual ledger for one finished slice.

        ``padded_words`` counts only *materialized* padded neighbor-matrix
        words: the lazy ``slc.npad`` is charged iff some backend forced it,
        plus any per-bin matrices a binned backend built (``extra``). The
        host and dense lanes never materialize ``npad``, which is exactly
        the waste the skew-aware planner's A/B measures.
        """
        s = self.stats
        if s is None:
            return
        with self._stats_lock:
            if slc._npad is not None:
                s.padded_words += slc.padded_words
            s.padded_words += int(extra)
            if slc.row_vals is not None:
                s.actual_words += len(slc.row_vals)

    def _backend_for(self, slc: BoxSlice) -> str:
        if self._backend_takes_box:
            return self.pick_backend(slc.n_edges, slc.wx, slc.wy, slc.box)
        return self.pick_backend(slc.n_edges, slc.wx, slc.wy)

    # -- edge padding to bucketed device shapes ------------------------------

    def _bucket_edges(self, slc: BoxSlice, chunk: int):
        """Pad (eu, ev) to a power-of-two length with pad-row references.

        The pad row is all-SENTINEL, so padded slots intersect to zero —
        no validity mask needed, and jit traces are shared across boxes.
        """
        m = slc.n_edges
        mb = _pow2(m, lo=min(chunk, 256))
        pad_row = np.int32(len(slc.rows))
        eu = np.full(mb, pad_row, np.int32)
        ev = np.full(mb, pad_row, np.int32)
        eu[:m] = slc.eu
        ev[:m] = slc.ev
        return eu, ev

    # -- backends ------------------------------------------------------------

    def _lane_inputs(self, slc: BoxSlice, chunk: int):
        """The binary lane's padded host inputs, then their device copies:
        the ``lane.pad`` and ``lane.put`` spans of one box. Returns
        ``((npad, eu, ev) on the device, bytes shipped)``."""
        with maybe_span(self.tracer, "lane.pad"):
            host = (slc.npad,) + self._bucket_edges(slc, chunk)
        with maybe_span(self.tracer, "lane.put"):
            dev = tuple(jnp.asarray(x) for x in host)
        return dev, sum(x.nbytes for x in host)

    def _count_binary(self, slc: BoxSlice) -> int:
        chunk = min(self.chunk, _pow2(slc.n_edges, lo=256))
        (npad, eu, ev), nbytes = self._lane_inputs(slc, chunk)
        with maybe_span(self.tracer, "lane.run"):
            total = _count_chunked(npad, eu, ev, chunk=chunk)
            out = int(total)
        kernel_ledger.note(1, bytes_in=nbytes, bytes_out=total.dtype.itemsize)
        return out

    def _count_host(self, slc: BoxSlice) -> int:
        """Σ_edges |N(u) ∩ N(v)| on the host, pure numpy.

        Same binary-search probing as ``_count_chunked``, vectorized as ONE
        ``searchsorted`` per edge chunk: each edge's b-row is lifted into a
        disjoint int64 key range (row_id · (SENTINEL+1) + value), so the
        flattened key array stays sorted and a row-local probe becomes a
        global one. numpy's searchsorted/compare kernels release the GIL,
        which makes this the backend that scales across the async
        scheduler's workers on CPU hosts — XLA's CPU client serializes
        concurrent executions, so the jax lanes cannot (on TPU the device
        lanes overlap asynchronously instead).
        """
        m = slc.n_edges
        if m == 0:
            return 0
        off, vals = slc.row_off, slc.row_vals
        if off is None:
            # externally-built slices: recover the compact CSR from npad
            mask = slc.npad != SENTINEL
            deg = mask.sum(axis=1).astype(np.int64)
            off = np.concatenate([np.zeros(1, np.int64), np.cumsum(deg)])
            vals = slc.npad[mask]
        deg = np.diff(off)
        # keys lift each edge's sorted neighbor run into a disjoint range
        # (edge_pos · stride + value), so the concatenation stays sorted
        # and ONE global lower-bound probes every edge at once. stride only
        # has to clear the value domain — int32 keys when (chunk_edges ·
        # stride) fits, halving the memory traffic of the lift
        stride = np.int64(max(int(vals.max(initial=0)) + 1, 1))
        max32 = int((np.iinfo(np.int32).max - stride + 1) // stride)

        def lift(rows: np.ndarray) -> np.ndarray:
            d = deg[rows]
            n = int(d.sum())
            if n == 0:
                return np.zeros(0, np.int64)
            r0 = np.repeat(off[rows], d)
            within = np.arange(n) - np.repeat(np.cumsum(d) - d, d)
            if len(rows) <= max32:
                rid = np.repeat(
                    np.arange(len(rows), dtype=np.int32)
                    * np.int32(stride), d)
                return vals[r0 + within] + rid
            rid = np.repeat(np.arange(len(rows), dtype=np.int64), d)
            return vals[r0 + within].astype(np.int64) + rid * stride

        # chunk the edge list so the lifted key arrays stay ~bounded; the
        # probe work scales with real neighbor entries (CSR), never the
        # padded width a box hub row inflates
        load = np.cumsum(deg[slc.eu] + deg[slc.ev])
        total = 0
        s = 0
        while s < m:
            base = int(load[s - 1]) if s else 0
            e = int(np.searchsorted(load, base + 4_000_000, side="right"))
            e = min(max(e, s + 1), s + max(1, max32))
            ak = lift(slc.eu[s:e])
            bk = lift(slc.ev[s:e])
            if len(ak) > len(bk):
                ak, bk = bk, ak          # probe the smaller into the larger
            if len(ak) and len(bk):
                pos = np.searchsorted(bk, ak)
                np.minimum(pos, bk.size - 1, out=pos)
                total += int((bk[pos] == ak).sum())
            s = e
        return total

    def _count_dense(self, slc: BoxSlice) -> Optional[int]:
        """Σ mask ⊙ (Ax Ayᵀ) over the *compacted* z domain.

        Columns span only the z values that actually occur in the slice's
        neighbor lists (renumbered), so the one-hot rows scale with the box,
        not with V. The one-hots are scattered straight from the slice's
        compact CSR (``row_off``/``row_vals``) — the dense lane never
        materializes the padded ``npad`` matrix, so a hub box routed here
        pays zero padded words. Returns ``None`` when the exact one-hot
        footprint would exceed ``dense_words_cap`` (e.g. a pinned hub row
        whose z domain is its full million-neighbor list) — the
        dispatcher's pre-materialize estimate cannot see the z domain, so
        the hard cap is enforced here and the caller falls back to the
        binary backend.
        """
        off, vals = slc.row_off, slc.row_vals
        if off is None:
            # externally-built slices: recover the compact CSR from npad
            mask = slc.npad != SENTINEL
            d = mask.sum(axis=1).astype(np.int64)
            off = np.concatenate([np.zeros(1, np.int64), np.cumsum(d)])
            vals = slc.npad[mask]
        zdom = np.unique(vals)
        if len(zdom) == 0:
            return 0
        rows_x = np.unique(slc.eu)
        rows_y = np.unique(slc.ev)
        if (len(rows_x) + len(rows_y)) * len(zdom) > self.dense_words_cap:
            return None
        deg_all = np.diff(off)

        def one_hot(rows_local):
            a = np.zeros((len(rows_local), len(zdom)), dtype=np.float32)
            d = deg_all[rows_local]
            n = int(d.sum())
            if n:
                rr = np.repeat(np.arange(len(rows_local)), d)
                idx = np.repeat(off[rows_local], d) + np.arange(n) \
                    - np.repeat(np.cumsum(d) - d, d)
                a[rr, np.searchsorted(zdom, vals[idx])] = 1.0
            return a

        ax, ay = one_hot(rows_x), one_hot(rows_y)
        mask = np.zeros((len(rows_x), len(rows_y)), dtype=np.float32)
        mask[np.searchsorted(rows_x, slc.eu),
             np.searchsorted(rows_y, slc.ev)] = 1.0
        if self.use_pallas_kernels:  # MXU tiling pays off on real hardware
            from repro.kernels.triangle_dense.ops import triangle_count
            return int(triangle_count(ax, ay, mask))
        # entries are exact in f32 (< 2^24 paths); the sum is not
        return int((mask * (ax @ ay.T)).sum(dtype=np.float64))

    def _count_pallas(self, slc: BoxSlice) -> int:
        """Per-edge |N(u) ∩ N(v)| on the Pallas rotation kernel, the edge
        rows gathered straight from the slice's compact CSR in bounded
        chunks — never the (edges × K) pair of padded matrices at once."""
        from repro.kernels.intersect.ops import intersect_count_rows
        return intersect_count_rows(slc.row_off, slc.row_vals, slc.eu,
                                    slc.row_off, slc.row_vals, slc.ev,
                                    use_pallas=True,
                                    interpret=not self.use_pallas_kernels)

    def _count_binned_slice(self, slc: BoxSlice) -> int:
        """Per-box degree-binned counting: the out-of-core analogue of the
        engine's global binned path (the ``degree_bins=True`` contract for
        store-backed sources). The slice's compact CSR rows are grouped into
        power-of-4 width classes (``pad_neighbors_binned``) and each edge
        probes its (bin_u, bin_v) pair's matrices via
        ``_count_rows_chunked`` — pad waste per row is bounded by the bin
        growth factor instead of the box-local max degree, and the global
        ``npad`` is never touched."""
        if slc.n_edges == 0:
            return 0
        row_bin, bins = pad_neighbors_binned(slc.row_off, slc.row_vals)
        bin_pos = np.zeros(max(1, len(row_bin)), dtype=np.int64)
        extra = 0
        for rows_b, npad_b in bins:
            bin_pos[rows_b] = np.arange(len(rows_b))
            extra += int(npad_b.size)
        bu = row_bin[slc.eu]
        bv = row_bin[slc.ev]
        live = (bu >= 0) & (bv >= 0)   # deg-0 rows intersect to nothing
        total = 0
        for i, j in sorted(set(zip(bu[live].tolist(), bv[live].tolist()))):
            sel = np.flatnonzero(live & (bu == i) & (bv == j))
            a_rows = bins[i][1][bin_pos[slc.eu[sel]]]
            b_rows = bins[j][1][bin_pos[slc.ev[sel]]]
            chunk = min(self.chunk, _pow2(len(sel), lo=256))
            total += int(_count_rows_chunked(jnp.asarray(a_rows),
                                             jnp.asarray(b_rows),
                                             chunk=chunk))
        self._note_padding(slc, extra=extra)
        return total

    def _count_fused(self, slc: BoxSlice) -> Optional[int]:
        """Whole-box triangle count in ONE device invocation: the fused
        Pallas frontier megakernel (``kernels.lftj_fused``). The triangle
        query ships as three box-restricted atoms in compact CSR form —
        the in-box edge list as R(x, y) plus the slice's neighbor lists
        re-keyed by the edge endpoints as S(x, z) and T(y, z) — so the
        entire per-level frontier leapfrog runs on-device instead of one
        staged launch per chunk. Returns ``None`` when the padded box
        falls outside the kernel's VMEM envelope; the caller falls back
        to the staged lanes."""
        if slc.n_edges == 0:
            return 0
        from repro.kernels.lftj_fused.ops import FusedUnsupported, fused_count
        off, vals = slc.row_off, slc.row_vals
        if off is None:
            # externally-built slices: recover the compact CSR from npad
            mask = slc.npad != SENTINEL
            deg = mask.sum(axis=1).astype(np.int64)
            off = np.concatenate([np.zeros(1, np.int64), np.cumsum(deg)])
            vals = slc.npad[mask]
        deg = np.diff(off)

        def sub_csr(local_rows: np.ndarray):
            d = deg[local_rows]
            n = int(d.sum())
            so = np.concatenate([np.zeros(1, np.int64),
                                 np.cumsum(d, dtype=np.int64)])
            if n == 0:
                return so, vals[:0]
            r0 = np.repeat(off[local_rows], d)
            within = np.arange(n) - np.repeat(np.cumsum(d) - d, d)
            return so, vals[r0 + within]

        # R(x, y): the in-box edges, grouped by global source id (rows is
        # sorted, so local-id order == global-id order)
        gu = slc.rows[slc.eu]
        gv = slc.rows[slc.ev]
        order = np.lexsort((gv, gu))
        gu_s, gv_s = gu[order], gv[order]
        keys0, counts0 = np.unique(gu_s, return_counts=True)
        off0 = np.concatenate([np.zeros(1, np.int64),
                               np.cumsum(counts0, dtype=np.int64)])
        uniq_u = np.unique(slc.eu)
        uniq_v = np.unique(slc.ev)
        off1, vals1 = sub_csr(uniq_u)
        off2, vals2 = sub_csr(uniq_v)
        csrs = ((keys0, off0, gv_s),
                (slc.rows[uniq_u], off1, vals1),
                (slc.rows[uniq_v], off2, vals2))
        try:
            return fused_count(((0, 1), (0, 2), (1, 2)), csrs, 3,
                               interpret=not self.use_pallas_kernels)
        except FusedUnsupported:
            return None

    def _note_fallback(self, field: str) -> None:
        if self.stats is not None:
            with self._stats_lock:
                setattr(self.stats, field, getattr(self.stats, field) + 1)

    def _count_slice(self, slc: BoxSlice) -> int:
        with kernel_ledger.attach(tracer=self.tracer) as kl:
            out, op = self._count_slice_dispatch(slc)
        if self.stats is not None and kl.invocations:
            with self._stats_lock:
                self.stats.device_invocations += kl.invocations
                self.stats.device_transfer_bytes += kl.transfer_bytes
                self.stats.max_box_device_invocations = max(
                    self.stats.max_box_device_invocations, kl.invocations)
        if self.metrics is not None:
            self.metrics.note_kernel(kl, op=op)
        return out

    def _count_slice_dispatch(self, slc: BoxSlice) -> Tuple[int, str]:
        """Counts one slice; returns ``(count, backend_op)`` so the
        caller can label the box's kernel launches (``kernel.*{op=..}``)
        with the backend that actually ran, fallbacks included."""
        be = self._backend_for(slc)
        if be == "fused":
            out = self._count_fused(slc)
            if out is not None:
                if self.stats is not None:
                    with self._stats_lock:
                        self.stats.n_fused_boxes += 1
                self._note_padding(slc)
                return out, "fused"
            # box outside the fused VMEM envelope: fall back to the
            # staged kernel lane (same launch cadence as before the
            # megakernel existed)
            self._note_fallback("n_fused_fallbacks")
            be = "pallas" if self.use_pallas_kernels else "binary"
        if be == "dense":
            out = self._count_dense(slc)
            if out is not None:
                if self.stats is not None:
                    with self._stats_lock:
                        self.stats.n_dense_boxes += 1
                self._note_padding(slc)
                return out, "dense"
            # one-hot footprint over the cap: fall back. The box is above
            # the dense crossover, hence inside the pallas mid-band — keep
            # the kernel backend when the platform supports it
            self._note_fallback("n_dense_fallbacks")
            be = "pallas" if self.use_pallas_kernels else "binary"
        if (be == "pallas" and slc.pad_shape[1] > INTERSECT_MAX_K) or (
                be == "binary" and not self.degree_bins
                and slc.padded_words > _PADDED_WORDS_CAP):
            # a row wider than the intersect kernel's VMEM envelope, or a
            # padded slice too big to ship (a hub row of a minmax-oriented
            # store): the host lane reads the compact CSR at any width
            self._note_fallback("n_padded_fallbacks")
            be = "host"
        if self.stats is not None:
            with self._stats_lock:
                if be == "pallas":
                    self.stats.n_pallas_boxes += 1
                elif be == "host":
                    self.stats.n_host_boxes += 1
                else:
                    self.stats.n_binary_boxes += 1
        if be == "pallas":
            out = self._count_pallas(slc)
        elif be == "host":
            out = self._count_host(slc)
        elif self.degree_bins:
            # binned backends self-record their padded extra
            return self._count_binned_slice(slc), "binned"
        else:
            out = self._count_binary(slc)
        self._note_padding(slc)
        return out, be

    def _list_slice(self, slc: BoxSlice) -> Optional[np.ndarray]:
        """One box's triangles (global vertex ids), in edge order. The
        device returns the box's (edges × K) hit mask; the triangles are
        read off its nonzeros here, so one pass lists the box exactly.
        Deterministic per slice, so serial and parallel runs produce
        identical per-box arrays."""
        # listing always runs the intersection path (dense is count-only),
        # so no backend counters are recorded here
        chunk = min(self.chunk, _pow2(slc.n_edges, lo=256))
        (npad, eu, ev), _ = self._lane_inputs(slc, chunk)
        with maybe_span(self.tracer, "lane.run"):
            hits = np.asarray(_list_hits(npad, eu, ev, chunk=chunk))
        self._note_padding(slc)
        with maybe_span(self.tracer, "lane.readout"):
            e, j = np.nonzero(hits[:slc.n_edges])
            if len(e) == 0:
                return None
            u = slc.eu[e]
            tris = np.stack([slc.rows[u], slc.rows[slc.ev[e]],
                             slc.npad[u, j]], axis=1).astype(np.int64)
            device = getattr(self.source, "device", None)
            if device is not None:
                device.write_words(3 * len(tris))
            return tris

    # -- async scheduler (workers > 1) ----------------------------------------
    # The pool/queue machinery itself lives in the module-level
    # ``run_box_queue`` so the generic ``repro.query.QueryEngine`` drains
    # its n-dimensional box queue through the exact same turnstile (same
    # serialized-fetch determinism contract, same in-flight window, same
    # telemetry) — this class only supplies the triangle-specific stages.

    def _est_slice_words(self, box) -> int:
        """Raw CSR words ``_materialize`` will read for ``box``, estimated
        from the resident degree index (exact for the uncached source: the
        same row ranges are summed that the materializer reads)."""
        ip = np.asarray(self.source.indptr)
        nv = self.source.n_nodes
        lx, hx, ly, hy = box
        lx_, hx_ = max(int(lx), 0), min(int(hx), nv - 1)
        ly_, hy_ = max(int(ly), 0), min(int(hy), nv - 1)
        if hx_ < lx_ or hy_ < ly_:
            return 0
        words = int(ip[hx_ + 1] - ip[lx_])
        for seg_lo, seg_hi in ((ly_, min(hy_, lx_ - 1)),
                               (max(ly_, hx_ + 1), hy_)):
            if seg_hi >= seg_lo:
                words += int(ip[seg_hi + 1] - ip[seg_lo])
        return words

    def _queue_order(self, boxes: List) -> List[int]:
        """Priority order the shared queue is drained in — the
        ``sharding.box_queue_order`` policy: LPT-first for pure in-memory
        sources (only makespan matters), plan order when a ``SliceCache``
        or charged ``BlockDevice`` is attached (adjacent boxes share row
        blocks in plan order, and — because builds are serialized in queue
        order — this keeps the device's LRU frame hits and the cache's
        hit/miss *sequence* identical to the ``workers=1`` run; LPT order
        measured ~1.6x the block reads on the out-of-core smoke workload).
        """
        from repro.parallel.sharding import box_queue_order
        ledger = isinstance(self.source, SliceCache) \
            or getattr(self.source, "device", None) is not None
        return box_queue_order([self._est_slice_words(b) for b in boxes],
                               ledger_sensitive=ledger)

    def _fetch_with_words(self, box) -> Tuple[object, int]:
        """``run_box_queue`` fetch stage: the box's source reads + their
        raw word count (the window-admission correction)."""
        fetched = self._fetch(box)
        return fetched, (fetched[-1] if fetched is not None else 0)

    def _build_slice(self, fetched) -> Optional[BoxSlice]:
        """``run_box_queue`` build stage: numpy compaction (no source
        access); ``None`` drops empty boxes before the backend runs."""
        slc = self._compact(fetched)
        if slc is None or slc.n_edges == 0:
            return None
        self._note(slc)
        return slc

    def _run_parallel(self, boxes: List, work: Callable) -> List:
        """Run ``work(slc)`` for every box on the shared worker pool
        (``run_box_queue``): per-box results in *plan order* (``None`` for
        empty boxes) so callers reduce deterministically regardless of
        completion order."""
        results, tele = run_box_queue(
            boxes, order=self._queue_order(boxes),
            est_words=self._est_slice_words,
            fetch=self._fetch_with_words,
            build=self._build_slice,
            work=work,
            workers=self.workers,
            inflight_items=self.inflight_boxes,
            inflight_words=self.inflight_words,
            tracer=self.tracer)
        if self.stats is not None:
            merge_queue_telemetry(self.stats, tele, self._stats_lock,
                                  inflight_boxes=self.inflight_boxes,
                                  metrics=self.metrics)
        return results

    # -- public entry points --------------------------------------------------

    def count_box(self, box, x_slab=None) -> int:
        """One-off execution of a single box (no prefetch pipeline)."""
        slc = self._materialize(box, x_slab=x_slab)
        if slc is None or slc.n_edges == 0:
            return 0
        self._note(slc)
        return self._count_slice(slc)

    def run_count(self, boxes) -> int:
        boxes = list(boxes)
        if self.workers > 1 and len(boxes) > 1:
            results = self._run_parallel(boxes, self._count_slice)
            # deterministic reduction: fixed box order, not arrival order
            return sum(r for r in results if r is not None)
        total = 0
        count = wrap_stage(self.tracer, "box.compute", self._count_slice)
        pf = self._stream(boxes)
        try:
            for slc in wrap_iter(self.tracer, "box.wait", pf):
                if slc is None or slc.n_edges == 0:
                    continue
                self._note(slc)
                total += count(slc)
        finally:
            # a consumer-side error must not leave the producer thread
            # reading the store (and charging the device) in the background
            pf.close()
        return total

    def run_list(self, boxes) -> np.ndarray:
        """Enumerate triangles across the box stream (global vertex ids).

        Each box is listed in one device pass (``_list_slice``). With
        ``workers>1`` boxes run on the async scheduler and the per-box
        arrays concatenate in fixed box order — identical output to the
        sequential run.
        """
        boxes = list(boxes)
        out: List[np.ndarray] = []
        if self.workers > 1 and len(boxes) > 1:
            out = [p for p in self._run_parallel(boxes, self._list_slice)
                   if p is not None]
        else:
            lst = wrap_stage(self.tracer, "box.compute", self._list_slice)
            pf = self._stream(boxes)
            try:
                for slc in wrap_iter(self.tracer, "box.wait", pf):
                    if slc is None or slc.n_edges == 0:
                        continue
                    self._note(slc)
                    tris = lst(slc)
                    if tris is not None:
                        out.append(tris)
            finally:
                pf.close()
        with maybe_span(self.tracer, "list.concat"):
            if not out:
                return np.zeros((0, 3), dtype=np.int64)
            return np.concatenate(out)
