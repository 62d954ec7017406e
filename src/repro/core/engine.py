"""TriangleEngine: planner + facade over the streaming box executor.

The engine is split into two layers (out-of-core refactor):

  * **planner** (this module)    — orientation/CSR preparation, the box plan
    (``core.boxing.plan_boxes`` in memory, ``plan_boxes_from_degrees`` from
    the resident degree index when the graph lives in a
    ``data.edgestore.EdgeStore``), per-box backend dispatch by edge density,
    and shard scheduling. The public ``TriangleEngine`` API is unchanged.
  * **streaming executor** (``core.executor.StreamingExecutor``) — pulls
    boxes from a work queue and materializes, per box, a vertex-renumbered
    *compacted* neighbor slice (never the global V×K ``npad``), overlapping
    host-side slice construction with device compute via
    ``data.pipeline.Prefetcher``. Source reads are charged to a
    ``core.iomodel.BlockDevice`` so ``EngineStats`` carries measured block
    I/Os (comparable against the paper's Thm. 10 bound).

Sharded execution (the "Boxes" rule of ``repro.parallel.sharding``) no
longer replicates the padded neighbor matrix: each shard receives only the
renumbered neighbor rows its boxes reference (``shard_local_slices``), so
per-device memory scales with the box slice, not the graph. With
``degree_bins=True`` the shard path runs one kernel per degree-bin pair on
``pad_neighbors_binned``-width matrices.

With ``cache_words > 0`` the source is wrapped in an LRU
``core.executor.SliceCache``: row blocks that adjacent boxes re-read
(same-stripe x-slabs, shared y-slices) are served from host memory instead
of re-charging the block device, so ``EngineStats.block_reads`` drops while
counts stay identical. ``TriangleEngine.ingest`` closes the remaining gap
to "graphs larger than RAM": it builds the store itself with bounded
memory (``data.edgestore.EdgeStoreWriter`` external-sort ingest).

Usage::

    eng = TriangleEngine(src, dst, mem_words=1 << 16)   # in-memory
    eng = TriangleEngine(store="graph.csr", mem_words=1 << 16)  # out-of-core
    eng = TriangleEngine(store="graph.csr", mem_words=1 << 16,
                         workers=4)    # async box scheduler (same output)
    eng = TriangleEngine.ingest("graph.csr", batch_iter,         # bounded-
                                ingest_budget_words=1 << 20,     # memory
                                mem_words=1 << 16,               # ingest
                                cache_words=1 << 14)
    n   = eng.count()
    tri = eng.list()          # (n, 3) canonical (min, mid, max) rows
    eng.stats                 # boxes, backends, shards, cache, block I/Os
"""

from __future__ import annotations

import json
import os
import tempfile
import time
import warnings
from dataclasses import dataclass, field
from functools import partial
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.shard_map import shard_map
from jax.sharding import PartitionSpec as P

from repro.data.edgestore import EdgeStore, InMemoryEdgeSource
from repro.obs.trace import maybe_span
from repro.parallel.sharding import (balanced_box_schedule, box_mesh,
                                     shard_local_slices)

from .executor import SliceCache, StreamingExecutor, _pow2
from .iomodel import BlockDevice
from .lftj_jax import (SENTINEL, _count_chunked, _count_rows_chunked,
                       _list_chunked, _list_pairs_chunked,
                       _gather_hits, csr_from_edges, orient_edges,
                       pad_neighbors, pad_neighbors_binned)

BACKENDS = ("auto", "binary", "dense", "pallas", "host", "fused")

# dense-path feasibility guard: one-hot words per box (slice-scaled estimate)
_DENSE_WORDS_CAP = 64_000_000


@dataclass
class EngineStats:
    """What one ``count()`` / ``list()`` call actually executed.

    The engine resets this on every ``count()`` / ``list()`` entry and
    fills it as the run proceeds, so after a call it is a faithful record
    of *that* run: the box plan size, the backend mix the density dispatch
    chose, shard shapes, streaming working-set peaks, slice-cache hits, and
    the block I/Os measured on the attached ``iomodel.BlockDevice`` (the
    numbers ``benchmarks/outofcore.py`` compares against the paper's
    Thm. 10 bound). All counters are plain ints/lists — cheap to snapshot
    or serialize.
    """

    n_boxes: int = 0
    n_dense_boxes: int = 0
    n_binary_boxes: int = 0
    n_pallas_boxes: int = 0
    n_host_boxes: int = 0
    n_fused_boxes: int = 0             # whole box on the fused megakernel
    # boxes routed to fused/dense that fell outside the lane's envelope
    # (VMEM budget / one-hot cap) and ran on the pallas or binary lane,
    # and pallas/binary boxes outside theirs (a row wider than
    # ``intersect.ops.MAX_K``; a padded slice over the executor's
    # ``_PADDED_WORDS_CAP``) that ran on the host lane
    n_fused_fallbacks: int = 0
    n_dense_fallbacks: int = 0
    n_padded_fallbacks: int = 0
    # listings whose vertex ids need more than 21 bits, so three of them
    # do not pack into one int64 key: sorted by ``np.lexsort`` instead
    n_canonical_fallbacks: int = 0
    # per-box device ledger (kernels/ledger): launches + padded transfer
    # bytes across every kernel lane — the measured basis of the fused
    # kernel's >=10x launch-reduction claim
    device_invocations: int = 0
    device_transfer_bytes: int = 0
    max_box_device_invocations: int = 0
    n_shards: int = 1
    n_rescans: int = 0
    dense_threshold: float = 0.0
    shard_edges: List[int] = field(default_factory=list)
    # skew-aware planning (skew="heavy_light"): the plan's lane mix plus
    # the padded-vs-actual word ledger the uniform/heavy-light A/B compares
    skew: str = "uniform"
    heavy_threshold: int = 0           # hub degree cut the plan used
    n_hub_boxes: int = 0               # both ranges heavy -> dense/pallas
    n_light_boxes: int = 0             # both ranges light -> host lane
    n_mixed_boxes: int = 0             # one heavy side   -> host lane
    padded_words: int = 0              # materialized padded-matrix words
    actual_words: int = 0              # real neighbor entries processed
    # async box scheduler (workers > 1): queue-wait/overlap/utilization
    # telemetry plus the observed in-flight peaks (the budget the window
    # promises to respect)
    n_workers: int = 1
    inflight_boxes: int = 0            # configured window (0 = serial run)
    queue_wait_s: float = 0.0          # worker-seconds spent waiting
    build_s: float = 0.0               # worker-seconds building slices
    compute_s: float = 0.0             # worker-seconds in backends
    overlap_s: float = 0.0             # busy-seconds hidden by overlap
    # busy / (workers * wall); None when the run finished too fast to
    # measure (wall == 0 at perf_counter granularity — never a 0/0)
    worker_utilization: Optional[float] = None
    max_inflight_boxes: int = 0        # peak resident materialized slices
    max_inflight_words: int = 0        # peak resident raw slice words
    # streaming executor (out-of-core) accounting
    n_streamed_boxes: int = 0
    slice_words_read: int = 0          # raw CSR words DMA'd across all boxes
    max_slice_words: int = 0           # largest single-box DMA (working set)
    max_slice_padded_words: int = 0    # largest box-local padded matrix
    # measured block I/O on the attached BlockDevice (edge-store runs)
    block_reads: int = 0
    block_writes: int = 0
    word_reads: int = 0
    # LRU slice cache (cache_words > 0): hits skip the device entirely,
    # so they show up as *missing* block_reads relative to a cache-off run
    cache_hits: int = 0
    cache_misses: int = 0
    cache_hit_words: int = 0
    # sharded-path device array shapes (non-replicated slices)
    local_npad_shape: Optional[Tuple[int, int, int]] = None
    shard_rows: List[int] = field(default_factory=list)
    source: str = "memory"

    @property
    def cache_hit_rate(self) -> float:
        total = self.cache_hits + self.cache_misses
        return self.cache_hits / total if total else 0.0

    @property
    def padding_ratio(self) -> float:
        """Materialized padded words per actual neighbor word (1.0 = no
        padded matrix was ever built beyond the real entries)."""
        return self.padded_words / self.actual_words \
            if self.actual_words else 0.0

    def as_info(self) -> dict:
        """Legacy info dict (triangle_count_boxed_vectorized compat)."""
        return {"n_boxes": self.n_boxes, "n_dense_boxes": self.n_dense_boxes,
                "n_shards": self.n_shards, "n_rescans": self.n_rescans}


# ---------------------------------------------------------------------------
# measured density crossover (binary-search vs dense MXU formulation),
# persisted per (jax backend, device kind) under ~/.cache/repro
# ---------------------------------------------------------------------------

_crossover_memo: dict = {}


def _crossover_cache_file() -> str:
    base = os.environ.get("REPRO_CACHE_DIR") \
        or os.path.join(os.path.expanduser("~"), ".cache", "repro")
    return os.path.join(base, "crossover.json")


class _crossover_file_lock:
    """Inter-process lock for the crossover cache's read-modify-write.

    The JSON store itself is written atomically (tmp + ``os.replace``), but
    two processes remeasuring concurrently still race load→merge→store and
    the slower one clobbers the faster one's entries (lost update — exactly
    what happens when pytest workers calibrate side by side under one
    ``REPRO_CACHE_DIR``). An ``flock`` on a sibling ``.lock`` file
    serializes the whole read-modify-write; platforms without ``fcntl``
    (or unwritable cache dirs) degrade to the old lock-free behaviour
    rather than failing execution."""

    def __init__(self):
        self._f = None

    def __enter__(self):
        try:
            import fcntl
            path = _crossover_cache_file() + ".lock"
            os.makedirs(os.path.dirname(path), exist_ok=True)
            self._f = open(path, "a+")
            fcntl.flock(self._f.fileno(), fcntl.LOCK_EX)
        except (ImportError, OSError):
            if self._f is not None:
                self._f.close()
                self._f = None
        return self

    def __exit__(self, *exc):
        if self._f is not None:
            try:
                import fcntl
                fcntl.flock(self._f.fileno(), fcntl.LOCK_UN)
            except (ImportError, OSError):
                pass
            self._f.close()
            self._f = None
        return False


def _crossover_load() -> dict:
    try:
        with open(_crossover_cache_file()) as f:
            data = json.load(f)
        return data if isinstance(data, dict) else {}
    except (OSError, ValueError):
        return {}


def _crossover_store(data: dict) -> None:
    path = _crossover_cache_file()
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        fd, tmp = tempfile.mkstemp(dir=os.path.dirname(path), suffix=".tmp")
        with os.fdopen(fd, "w") as f:
            json.dump(data, f, indent=2, sort_keys=True)
        os.replace(tmp, path)
    except OSError:
        pass  # a read-only home must never break execution


def _active_prefix() -> str:
    """Calibration namespace of the hardware this process runs on: JAX
    backend + device kind (e.g. ``cpu:cpu``, ``tpu:TPU v4``). Every
    crossover entry is keyed under it, so CPU-measured values never leak
    onto real TPU and vice versa."""
    dev = jax.devices()[0]
    return f"{jax.default_backend()}:{getattr(dev, 'device_kind', '?')}"


_remeasure_handled = False


def _maybe_clear_remeasure() -> None:
    """``REPRO_CROSSOVER_REMEASURE=1``: drop the *active* backend's
    cached entries once per process (other backends' calibrations in the
    shared file survive), then fall through to normal measure-and-persist
    — so a forced remeasure happens once, not on every call."""
    global _remeasure_handled
    if _remeasure_handled:
        return
    _remeasure_handled = True
    if os.environ.get("REPRO_CROSSOVER_REMEASURE", "") in ("", "0"):
        return
    prefix = _active_prefix() + ":"
    with _crossover_file_lock():
        data = _crossover_load()
        kept = {k: v for k, v in data.items() if not k.startswith(prefix)}
        if len(kept) != len(data):
            _crossover_store(kept)
    for k in list(_crossover_memo):
        if k.startswith(prefix):
            del _crossover_memo[k]


def _cached_crossover(suffix: str, nv: int, measure) -> float:
    """Process-memoized, file-persisted crossover for the active backend:
    ``measure()`` runs only when neither the memo nor the JSON cache has a
    valid entry for ``<backend>:<device_kind>:nv<nv><suffix>``."""
    _maybe_clear_remeasure()
    key = f"{_active_prefix()}:nv{nv}{suffix}"
    if key in _crossover_memo:
        return _crossover_memo[key]
    cached = _crossover_load().get(key)
    if isinstance(cached, (int, float)) and 0.0 < cached <= 1.0:
        _crossover_memo[key] = float(cached)
        return float(cached)
    value = measure()
    _crossover_memo[key] = value
    # merge-under-lock: re-load inside the file lock so a concurrent
    # process's freshly-persisted keys survive this store (the two-process
    # remeasure race regression-tested in tests/test_crossover_cache.py)
    with _crossover_file_lock():
        data = _crossover_load()
        data[key] = value
        _crossover_store(data)
    return value


def measure_dense_crossover(nv: int = 256, repeats: int = 3,
                            seed: int = 0) -> float:
    """Lowest box density where the dense MXU formulation beats the
    binary-search backend, measured once per (jax backend, device kind).

    The measurement is persisted to a JSON cache
    (``$REPRO_CACHE_DIR/crossover.json``, default ``~/.cache/repro``)
    keyed by backend + device kind so a fleet of processes on the same
    hardware calibrates once, not per process — and a CPU-measured value
    is never consulted on TPU. Set ``REPRO_CROSSOVER_REMEASURE=1`` to
    drop the active backend's entries and measure fresh (e.g. after a
    driver/runtime upgrade); other backends' entries are untouched. Falls
    back to 1.0 (never dense) only if dense never wins on the sampled
    grid.
    """
    return _cached_crossover(
        "", nv, lambda: _measure_dense_crossover(nv, repeats, seed))


def _measure_dense_crossover(nv: int, repeats: int, seed: int) -> float:
    rng = np.random.default_rng(seed)
    densities = (0.01, 0.02, 0.05, 0.10, 0.20, 0.40)
    crossover = 1.0
    for d in densities:
        adj = np.triu(rng.random((nv, nv)) < d, k=1)
        src, dst = np.nonzero(adj)
        if len(src) == 0:
            continue
        indptr, indices = csr_from_edges(src, dst, n_nodes=nv)
        npad = jnp.asarray(pad_neighbors(indptr, indices))
        eu = jnp.asarray(src, jnp.int32)
        ev = jnp.asarray(dst, jnp.int32)
        a = jnp.asarray(adj, jnp.float32)

        def t_binary():
            _count_chunked(npad, eu, ev, chunk=2048).block_until_ready()

        def t_dense():
            jnp.sum(a * (a @ a.T)).block_until_ready()

        t_binary(); t_dense()  # compile outside the timed region
        tb = min(_time(t_binary) for _ in range(repeats))
        td = min(_time(t_dense) for _ in range(repeats))
        if td < tb:
            crossover = d
            break
    return crossover


def _time(fn) -> float:
    t0 = time.perf_counter()
    fn()
    return time.perf_counter() - t0


def measure_pallas_crossover(nv: int = 256, repeats: int = 3,
                             seed: int = 0) -> float:
    """Lowest box density where the Pallas rotation-intersect kernel beats
    the binary-search backend — the measured lower edge of the mid-density
    'pallas band' (static default: dense crossover / 4).

    Calibrated the same way as ``measure_dense_crossover`` and persisted
    next to it in the same JSON cache (key suffix ``:pallas``), once per
    (jax backend, device kind); ``REPRO_CROSSOVER_REMEASURE=1`` refreshes.
    Off-TPU the kernel only runs in interpret mode — orders of magnitude
    slower than any alternative — so the measurement short-circuits to 1.0
    (band never active) without timing the interpreter; 'auto' dispatch
    additionally gates the band on ``use_pallas_kernels``, so this value
    only steers dispatch on real TPU hardware.
    """
    return _cached_crossover(
        ":pallas", nv,
        lambda: 1.0 if jax.default_backend() != "tpu"
        else _measure_pallas_crossover(nv, repeats, seed))


def _measure_pallas_crossover(nv: int, repeats: int, seed: int) -> float:
    from repro.kernels.intersect.ops import intersect_count

    rng = np.random.default_rng(seed)
    densities = (0.005, 0.01, 0.02, 0.05, 0.10, 0.20)
    crossover = 1.0
    for d in densities:
        adj = np.triu(rng.random((nv, nv)) < d, k=1)
        src, dst = np.nonzero(adj)
        if len(src) == 0:
            continue
        indptr, indices = csr_from_edges(src, dst, n_nodes=nv)
        npad_h = pad_neighbors(indptr, indices)
        npad = jnp.asarray(npad_h)
        eu = jnp.asarray(src, jnp.int32)
        ev = jnp.asarray(dst, jnp.int32)
        a_rows = npad_h[src]
        b_rows = npad_h[dst]

        def t_binary():
            _count_chunked(npad, eu, ev, chunk=2048).block_until_ready()

        def t_pallas():
            intersect_count(a_rows, b_rows, use_pallas=True,
                            interpret=False).block_until_ready()

        t_binary(); t_pallas()  # compile outside the timed region
        tb = min(_time(t_binary) for _ in range(repeats))
        tp = min(_time(t_pallas) for _ in range(repeats))
        if tp < tb:
            crossover = d
            break
    return crossover


def measure_fused_crossover(nv: int = 256, repeats: int = 3,
                            seed: int = 0) -> float:
    """Lowest box density where the fused per-box LFTJ megakernel
    (``kernels/lftj_fused``) beats the binary-search backend on a whole
    triangle box — the calibration behind the ``fused_threshold``
    dispatch knob.

    Persisted next to the dense and pallas crossovers in the same
    backend-keyed JSON cache (key suffix ``:fused``);
    ``REPRO_CROSSOVER_REMEASURE=1`` refreshes the active backend only.
    Off-TPU the megakernel runs in interpret mode, never competitively,
    so the measurement short-circuits to 1.0 (band never active) without
    timing the interpreter.
    """
    return _cached_crossover(
        ":fused", nv,
        lambda: 1.0 if jax.default_backend() != "tpu"
        else _measure_fused_crossover(nv, repeats, seed))


def _measure_fused_crossover(nv: int, repeats: int, seed: int) -> float:
    from repro.kernels.lftj_fused.ops import fused_count

    rng = np.random.default_rng(seed)
    densities = (0.005, 0.01, 0.02, 0.05, 0.10, 0.20)
    crossover = 1.0
    dims = ((0, 1), (0, 2), (1, 2))
    for d in densities:
        adj = np.triu(rng.random((nv, nv)) < d, k=1)
        src, dst = np.nonzero(adj)
        if len(src) == 0:
            continue
        indptr, indices = csr_from_edges(src, dst, n_nodes=nv)
        npad = jnp.asarray(pad_neighbors(indptr, indices))
        eu = jnp.asarray(src, jnp.int32)
        ev = jnp.asarray(dst, jnp.int32)
        keys = np.flatnonzero(np.diff(indptr) > 0).astype(np.int64)
        off = np.concatenate(
            [[0], np.cumsum(np.diff(indptr)[keys])]).astype(np.int64)
        csr = (keys, off, np.asarray(indices, np.int32))
        csrs = [csr, csr, csr]

        def t_binary():
            _count_chunked(npad, eu, ev, chunk=2048).block_until_ready()

        def t_fused():
            fused_count(dims, csrs, 3, interpret=False)

        t_binary(); t_fused()  # compile outside the timed region
        tb = min(_time(t_binary) for _ in range(repeats))
        tf = min(_time(t_fused) for _ in range(repeats))
        if tf < tb:
            crossover = d
            break
    return crossover


def sharded_count_program(mesh, chunk: int):
    """jit'd ``shard_map`` count over the mesh's "boxes" axis: each shard
    scans its own renumbered slice ``npad[s]`` and edge lists in
    ``chunk``-edge steps and returns one int32 partial."""

    @jax.jit
    @partial(shard_map, mesh=mesh,
             in_specs=(P("boxes", None, None), P("boxes", None),
                       P("boxes", None), P("boxes", None)),
             out_specs=P("boxes"), check_rep=False)
    def sharded_count(npad, eu, ev, ok):
        npad = npad[0]                      # this shard's local slice
        n_chunks = eu.shape[1] // chunk

        def body(carry, inp):
            u, v, valid = inp
            hit = _gather_hits(npad, npad, u, v)[1] & (valid[:, None] > 0)
            return carry + jnp.sum(hit.astype(jnp.int32)), None

        total, _ = jax.lax.scan(
            body, jnp.int32(0),
            (eu.reshape(n_chunks, chunk), ev.reshape(n_chunks, chunk),
             ok.reshape(n_chunks, chunk)))
        return total.reshape(1)

    return sharded_count


_CANONICAL_CHUNK = 1 << 15         # rows per pass of the pack and decode


def _sorted_packed_rows(tris: np.ndarray, bits: int) -> np.ndarray:
    """The rows of ``tris`` as ``(min, mid, max)``, in lexicographic
    order: a new C-contiguous (m, 3) int64 array, for ids below
    ``2**bits`` with ``3 * bits <= 63``.

    A min/max network orders each row, the three ids pack into one int64
    key (``lo << 2*bits | mid << bits | hi``), whose numeric order is the
    rows' lexicographic order; the keys sort in place and decode by shift
    and mask straight into the output rows. Packing and decoding go a
    cache-sized chunk of rows at a time, so the keys and the output are
    the only full-size arrays."""
    m, step = len(tris), _CANONICAL_CHUNK
    key = np.empty(m, np.int64)
    lo_buf, hi_buf = np.empty(step, np.int64), np.empty(step, np.int64)
    for s in range(0, m, step):
        a, b, c = tris[s:s + step].T
        k = key[s:s + step]
        lo, hi = lo_buf[:len(k)], hi_buf[:len(k)]
        np.minimum(a, b, out=lo)
        np.minimum(lo, c, out=lo)
        np.maximum(a, b, out=hi)
        np.maximum(hi, c, out=hi)
        np.add(a, b, out=k)
        k += c
        k -= lo
        k -= hi                     # the middle id
        k <<= bits
        k |= hi
        lo <<= 2 * bits
        k |= lo
    key.sort()
    mask = (1 << bits) - 1
    out = np.empty((m, 3), np.int64)
    for s in range(0, m, step):
        k, (lo, mid, hi) = key[s:s + step], out[s:s + step].T
        np.right_shift(k, 2 * bits, out=lo)
        np.right_shift(k, bits, out=mid)
        np.bitwise_and(mid, mask, out=mid)
        np.bitwise_and(k, mask, out=hi)
    return out


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------

class TriangleEngine:
    """Unified boxed/sharded/streaming triangle counting + listing.

    Parameters
    ----------
    src, dst : undirected edge endpoints (host numpy); omit when ``store``
        is given.
    store : path to a ``data.edgestore`` file (or an open ``EdgeStore``):
        the out-of-core source. The engine then keeps only the (V+1)-word
        degree index resident and streams per-box slices from disk, with
        block I/Os measured on ``device``.
    device : optional ``core.iomodel.BlockDevice`` charging source reads.
        Defaults to a fresh device for store-backed runs (block size
        ``io_block_words``, cache sized to the memory budget); ``None``
        for in-memory runs (no accounting).
    mem_words : memory budget for the box planner; ``None`` = one box.
    cache_words : LRU slice-cache budget (``core.executor.SliceCache``),
        split out of the overall host budget: plan with ``mem_words`` and
        spend ``cache_words`` *on top* caching row blocks that adjacent
        boxes re-read (same-stripe x-slabs, shared y-slices). Keeping it a
        separate knob leaves the box plan unchanged, so cache-on vs
        cache-off runs are directly comparable; total host footprint is
        ``mem_words + cache_words``. 0 disables the cache.
    orientation : 'minmax' (paper §2.3) or 'degree' (√|E| out-degree cap).
        Store-backed graphs carry their orientation in the file header.
    backend : 'auto' (density dispatch), or force 'binary' / 'dense' /
        'pallas' / 'host' / 'fused' for every box ('host' is the
        pure-numpy binary-search lane — the GIL-releasing backend the
        async scheduler's worker threads scale with on CPU hosts, where
        XLA serializes concurrent executions; 'fused' dispatches each
        whole box to the ``kernels/lftj_fused`` megakernel — one device
        invocation per box, interpret mode off-TPU — falling back per box
        to pallas/binary when outside the kernel's envelope).
    dense_threshold : box edge-density above which 'auto' picks the dense
        MXU formulation; the string 'measured' uses the persisted
        calibration (``measure_dense_crossover``).
    pallas_threshold : lower edge of the mid-density band 'auto' routes to
        the Pallas intersect kernel (only on TPU — see backend). Default
        ``dense_threshold / 4``; the string 'measured' uses the persisted
        calibration (``measure_pallas_crossover``, cached in the same
        ``crossover.json`` as the dense crossover).
    fused_threshold : density above which 'auto' prefers the fused
        per-box megakernel over the staged pallas band (TPU only).
        Default ``None`` keeps density dispatch off the fused lane
        (heavy/light hub boxes still route to it on TPU); the string
        'measured' uses the persisted ``measure_fused_crossover``
        calibration (key suffix ``:fused`` in the same cache).
    degree_bins : bin vertices by degree (power-of-4 widths) so padding is
        per-bin instead of global K = max degree (skewed graphs). In-memory
        engines run the global binned layout; store-backed engines bin
        *per box slice* inside the streaming executor (the out-of-core
        analogue — same counts, padding bounded by the bin growth factor
        instead of the box-local max degree). Sharded listing runs the
        per-bin-pair listing kernel. Never ignored, never a silent
        fallback.
    skew : 'uniform' (default, the mass-budgeted grid cutter) or
        'heavy_light': classify vertices heavy (degree >= heavy_threshold)
        vs light from the resident degree index and break every box range
        at class transitions, so each box is pure-class per axis. Hub-hub
        boxes (near-dense by construction) route to the dense lane, or to
        the fused megakernel when the one-hot footprint cannot fit and the
        platform compiles Pallas; light and mixed boxes route to the host
        searchsorted lane,
        which never materializes a padded matrix. Lane decisions are
        recorded in ``EngineStats`` (``n_hub_boxes`` / ``n_light_boxes`` /
        ``n_mixed_boxes``, ``padded_words`` vs ``actual_words``) for exact
        A/B against the uniform planner.
    heavy_threshold : hub degree cut for ``skew='heavy_light'``; default
        ``heavy_threshold_default`` (√(2·|E|)-style).
    devices : devices for box sharding; default ``jax.devices()``.
    chunk : edge-chunk length of the scan (peak memory O(chunk · K)).
    prefetch_depth : how many box slices the host builds ahead of the
        device (``data.pipeline.Prefetcher`` double-buffering).
    workers : worker threads of the async box scheduler. 1 (default) is
        the sequential oracle — one box in flight behind a Prefetcher.
        With ``workers > 1`` the box work-queue drains LPT-first across a
        thread pool (plan order when a slice cache is attached, preserving
        the serial read stream); counts and listings are reduced in fixed
        box order, so the output is identical to the ``workers=1`` run.
        The spawned pool is clamped to the hardware parallelism
        (``os.cpu_count()``): threads beyond the cores measurably thrash.
    inflight_boxes : in-flight window of the async scheduler — at most
        this many materialized slices resident at once (default
        ``2 * workers``), with total resident raw words additionally
        capped at ``inflight_boxes * mem_words`` when a budget is set.
        Host memory of a parallel run is therefore bounded by the window,
        not the box count.
    use_pallas_kernels : run kernels compiled (TPU) vs interpret; default
        only compiles on TPU.
    """

    def __init__(self, src: Optional[np.ndarray] = None,
                 dst: Optional[np.ndarray] = None, *,
                 store=None,
                 device: Optional[BlockDevice] = None,
                 io_block_words: int = 4096,
                 mem_words: Optional[int] = None,
                 cache_words: int = 0,
                 orientation: str = "minmax",
                 backend: str = "auto",
                 dense_threshold=0.05,
                 pallas_threshold=None,
                 fused_threshold=None,
                 degree_bins: bool = False,
                 skew: str = "uniform",
                 heavy_threshold: Optional[int] = None,
                 devices: Optional[Sequence] = None,
                 shard: str | bool = "auto",
                 chunk: int = 2048,
                 prefetch_depth: int = 2,
                 workers: int = 1,
                 inflight_boxes: Optional[int] = None,
                 use_pallas_kernels: Optional[bool] = None,
                 tracer=None,
                 metrics=None):
        if backend not in BACKENDS:
            raise ValueError(f"backend {backend!r} not in {BACKENDS}")
        if skew not in ("uniform", "heavy_light"):
            raise ValueError(
                f"skew {skew!r} not in ('uniform', 'heavy_light')")
        self.backend = backend
        # observability: span/event recorder (obs.trace.Tracer) and the
        # cross-layer MetricsRegistry; None by default — the traced-off
        # path is a single attribute check per site
        self.tracer = tracer
        self.metrics = metrics
        self.degree_bins = degree_bins
        self.skew = skew
        self.heavy_threshold = heavy_threshold
        self.chunk = int(chunk)
        self.mem_words = mem_words
        self.prefetch_depth = int(prefetch_depth)
        self.workers = max(1, int(workers))
        self.inflight_boxes = max(1, int(inflight_boxes)) \
            if inflight_boxes is not None else max(2, 2 * self.workers)
        if use_pallas_kernels is None:
            use_pallas_kernels = jax.default_backend() == "tpu"
        self.use_pallas_kernels = bool(use_pallas_kernels)

        self.devices = list(jax.devices()) if devices is None else list(devices)
        if shard == "auto":
            self.shard = len(self.devices) > 1
        else:
            self.shard = bool(shard)

        if dense_threshold == "measured":
            dense_threshold = measure_dense_crossover()
        self.dense_threshold = float(dense_threshold)
        # lower edge of the mid-density band 'auto' routes to the Pallas
        # intersect kernel (TPU only): static crossover/4 by default,
        # 'measured' uses the persisted calibration
        if pallas_threshold == "measured":
            pallas_threshold = measure_pallas_crossover()
        self.pallas_threshold = self.dense_threshold / 4.0 \
            if pallas_threshold is None else float(pallas_threshold)
        # density gate of the fused megakernel lane: None disables the
        # density route (hub boxes still take it on TPU), 'measured' uses
        # the :fused calibration from the same backend-keyed cache
        if fused_threshold == "measured":
            fused_threshold = measure_fused_crossover()
        self.fused_threshold = None if fused_threshold is None \
            else float(fused_threshold)

        if store is not None:
            if src is not None or dst is not None:
                raise ValueError("pass either (src, dst) or store=, not both")
            self.source = store if isinstance(store, EdgeStore) \
                else EdgeStore(store)
            if device is None:
                cache = max(2, (mem_words or (1 << 22)) // io_block_words)
                device = BlockDevice(block_words=io_block_words,
                                     cache_blocks=cache)
            self.source.attach_device(device)
            self.device = device
            self.orientation = self.source.orientation
            self.nv = self.source.n_nodes
            self.indptr = self.source.indptr
            self.indices = None          # never resident: streamed per box
            self.a = self.b = None
        else:
            if src is None or dst is None:
                raise ValueError(
                    "TriangleEngine needs either (src, dst) edge arrays or "
                    "store=<edge store path>")
            self.orientation = orientation
            a, b = orient_edges(np.asarray(src), np.asarray(dst), orientation)
            self.a, self.b = a, b
            self.nv = int(max(a.max(initial=-1), b.max(initial=-1))) + 1
            self.indptr, self.indices = csr_from_edges(a, b, n_nodes=self.nv) \
                if self.nv else (np.zeros(1, np.int64), np.zeros(0, np.int32))
            self.device = device
            self.source = InMemoryEdgeSource(self.indptr, self.indices,
                                             device=device,
                                             orientation=self.orientation)
        self.cache_words = int(cache_words)
        self._slice_cache: Optional[SliceCache] = None
        if self.cache_words > 0:
            self._slice_cache = SliceCache(self.source, self.cache_words,
                                           tracer=tracer)
            self.source = self._slice_cache
        if self.shard and self.indices is None:
            warnings.warn(
                "sharded execution stages the store-backed neighbor stream "
                "through host memory (one full sequential pass); for graphs "
                "larger than host RAM pass shard=False to keep the "
                "bounded-memory streaming path.", stacklevel=2)
        self._npad = None
        self._npad_host = None
        self._bins = None
        self._plan_cache: Optional[Tuple[Optional[int], list]] = None
        # box -> lane ("hub"/"light"/"mixed"), filled by the heavy_light
        # planner; the lane steers _pick_backend for planned boxes
        self._box_lane: dict = {}
        self._skew_threshold = 0
        self.stats = EngineStats(dense_threshold=self.dense_threshold,
                                 skew=self.skew)

    # -- lazy derived state --------------------------------------------------

    @property
    def npad_host(self) -> np.ndarray:
        """Global padded neighbor matrix — legacy accessor. The streaming
        paths never touch this; building it for a store-backed graph pages
        the whole neighbor stream in."""
        if self._npad_host is None:
            indptr, indices = self._resident_csr()
            self._npad_host = pad_neighbors(indptr, indices)
        return self._npad_host

    @property
    def npad(self) -> jnp.ndarray:
        if self._npad is None:
            self._npad = jnp.asarray(self.npad_host)
        return self._npad

    @property
    def bins(self):
        if self._bins is None:
            indptr, indices = self._resident_csr()
            self._bins = pad_neighbors_binned(indptr, indices)
        return self._bins

    def _resident_csr(self) -> Tuple[np.ndarray, np.ndarray]:
        if self.indices is not None:
            return self.indptr, self.indices
        # whole-graph staging read: bypass the slice cache — one sequential
        # pass can't benefit from it and would churn the entire LRU
        src = self._slice_cache.source if self._slice_cache is not None \
            else self.source
        _, indices = src.read_rows(0, self.nv - 1)
        return self.indptr, indices

    # -- streaming ingest ------------------------------------------------------

    @classmethod
    def ingest(cls, store_path, edges, *,
               orientation: str = "minmax",
               chunk_rows: int = 4096,
               align_words: int = 1024,
               ingest_budget_words: int = 1 << 22,
               prefetch_batches: bool = True,
               **engine_kw) -> "TriangleEngine":
        """Stream undirected edges into a chunked-CSR store, bounded-memory,
        and return a store-backed engine over it.

        ``edges`` is either an iterable of ``(src, dst)`` array batches
        (e.g. a generator parsing a file too big for RAM) or a single
        ``(src, dst)`` pair of arrays (sliced into batches internally).
        The batches flow through ``data.edgestore.EdgeStoreWriter``: spill
        runs under ``ingest_budget_words`` (4-byte words), then an external
        merge — peak ingest allocations stay ~2x the budget plus the O(V)
        degree index, so the graph never has to fit in RAM, *including*
        during ingest. With ``prefetch_batches`` the producer runs one
        batch ahead on a ``data.pipeline.Prefetcher`` thread, overlapping
        batch parsing with sort-and-spill.

        Remaining keyword arguments (``mem_words``, ``cache_words``,
        ``backend``, ...) are forwarded to the ``TriangleEngine``
        constructor for the returned engine.
        """
        from repro.data.edgestore import EdgeStoreWriter
        from repro.data.pipeline import Prefetcher, edge_batches

        if isinstance(edges, tuple) and len(edges) == 2 \
                and np.ndim(edges[0]) == 1:
            edges = edge_batches(*edges)
        writer = EdgeStoreWriter(store_path, orientation=orientation,
                                 chunk_rows=chunk_rows,
                                 align_words=align_words,
                                 budget_words=ingest_budget_words)
        it = Prefetcher(iter(edges), depth=1) if prefetch_batches \
            else iter(edges)
        try:
            with writer:
                for src, dst in it:
                    writer.add_edges(src, dst)
        finally:
            if isinstance(it, Prefetcher):
                it.close()
        return cls(store=writer.path, **engine_kw)

    # -- box planning ---------------------------------------------------------

    def plan(self) -> List[Tuple[int, int, int, int]]:
        """Box plan [(lx, hx, ly, hy)]; one unbounded box without a budget.

        Cached per ``mem_words`` — the probe/provision pass is the expensive
        host-side step and the plan is deterministic. In-memory graphs use
        the faithful TrieArray prober; store-backed graphs plan from the
        resident degree index (``plan_boxes_from_degrees``) so planning
        itself stays out-of-core.
        """
        with maybe_span(self.tracer, "engine.plan"):
            if self._plan_cache is not None \
                    and self._plan_cache[0] == self.mem_words:
                return self._plan_cache[1]
            boxes = self._plan_uncached()
            self._plan_cache = (self.mem_words, boxes)
            return boxes

    def _plan_uncached(self) -> List[Tuple[int, int, int, int]]:
        if self.nv == 0 or self.source.n_edges == 0:
            self._box_lane = {}
            return []
        # hy < lx pruning is only sound when every edge has x < y (minmax)
        prune = self.orientation == "minmax"
        if self.skew == "heavy_light":
            # skew-resistant plan straight from the resident degree index
            # (works identically in-memory and store-backed): pure-class
            # ranges per axis, lane metadata per box
            from .boxing import plan_boxes_heavy_light
            sp = plan_boxes_heavy_light(self.indptr, self.mem_words,
                                        monotone_prune=prune,
                                        heavy_threshold=self.heavy_threshold)
            self._box_lane = dict(zip(sp.boxes, sp.lanes))
            self._skew_threshold = sp.threshold
            return sp.boxes
        self._box_lane = {}
        if self.mem_words is None:
            return [(0, self.nv - 1, 0, self.nv - 1)]
        if self.indices is None:
            from .boxing import plan_boxes_from_degrees
            return plan_boxes_from_degrees(self.indptr, self.mem_words,
                                           monotone_prune=prune)
        from .boxing import plan_boxes
        from .triearray import TrieArray
        ta = TrieArray.from_edges(self.a, self.b)
        if ta.words() <= self.mem_words:
            return [(0, self.nv - 1, 0, self.nv - 1)]
        return plan_boxes(ta, self.mem_words, monotone_prune=prune)

    def _box_edges(self, box, source=None) -> Tuple[np.ndarray, np.ndarray,
                                                    int, int]:
        """In-box oriented edges (x ∈ [lx,hx], y ∈ [ly,hy]) + box widths."""
        eu, ev, wx, wy, _slab = self._box_edges_full(box, source)
        return eu, ev, wx, wy

    def _box_edges_full(self, box, source=None):
        """`_box_edges` plus the raw x-range slab, so a follow-up
        ``StreamingExecutor.count_box`` can reuse the already-charged DMA
        instead of re-reading the rows from the source."""
        src = self.source if source is None else source
        lx, hx, ly, hy = box
        lx_, hx_ = max(lx, 0), min(hx, self.nv - 1)
        ly_, hy_ = max(ly, 0), min(hy, self.nv - 1)
        if hx_ < lx_ or hy_ < ly_:
            return (np.zeros(0, np.int64), np.zeros(0, np.int64), 0, 0, None)
        ip, vals = src.read_rows(lx_, hx_)
        eu = np.repeat(np.arange(lx_, hx_ + 1), np.diff(ip))
        ev = vals.astype(np.int64)
        sel = (ev >= ly_) & (ev <= hy_)
        return (eu[sel], ev[sel], hx_ - lx_ + 1, hy_ - ly_ + 1, (ip, vals))

    def _staged_source(self):
        """Source for the *sharded* paths.

        Sharded execution concatenates every box's edges on the host anyway
        (the work-list is built before shard_map), so a store-backed graph
        is staged through host memory with ONE sequential charged pass
        (|E|/B block reads) instead of re-reading overlapping x-slabs per
        box and again per shard gather. Bounded-memory execution is the
        non-sharded streaming path.
        """
        if self.indices is not None:
            return self.source
        with maybe_span(self.tracer, "shard.stage"):
            indptr, indices = self._resident_csr()   # one charged full read
            return InMemoryEdgeSource(indptr, indices,
                                      orientation=self.orientation)

    def _pick_backend(self, n_edges: int, wx: int, wy: int,
                      box=None) -> str:
        """Density dispatch: dense above the crossover, Pallas for the
        mid-density band, binary-search otherwise.

        With ``skew="heavy_light"`` a planned ``box`` overrides density:
        hub-hub boxes go to the dense MXU lane (Pallas/binary when the
        one-hot footprint cannot fit), light and mixed boxes to the host
        searchsorted lane — neither ever materializes a padded matrix.

        The Pallas rotation-intersect kernel is only profitable compiled on
        real TPU hardware, so 'auto' routes mid-density boxes (density
        above ``pallas_threshold``, default dense crossover / 4) to it
        **only when**
        ``use_pallas_kernels`` is set (default: running on TPU). On CPU
        backends the kernel would run in interpret mode — orders of
        magnitude slower — so 'auto' never selects it there; force
        ``backend="pallas"`` to test that path explicitly.
        """
        if self.backend != "auto":
            return self.backend
        lane = self._box_lane.get(box) if box is not None else None
        if lane is not None:
            if lane == "hub":
                est_rows = min(wx, n_edges) + min(wy, n_edges)
                est_cols = min(self.nv, 16 * max(1, n_edges))
                if est_rows * est_cols <= _DENSE_WORDS_CAP:
                    return "dense"
                # hub boxes too big for the one-hot footprint dispatch
                # whole to the fused megakernel (compiled TPU only): one
                # launch instead of one per frontier level
                return "fused" if self.use_pallas_kernels else "binary"
            return "host"
        density = n_edges / max(1, wx * wy)
        # feasibility of the dense one-hots: the executor compacts rows to
        # the referenced endpoints (≤ min(width, edges) per side) and
        # columns to the z values occurring in the slice (≤ min(V, slice
        # neighbor entries)), so the cap is slice-scaled, not O(V) — dense
        # dispatch stays live on graphs far larger than memory
        est_rows = min(wx, n_edges) + min(wy, n_edges)
        est_cols = min(self.nv, 16 * max(1, n_edges))
        if density > self.dense_threshold \
                and est_rows * est_cols <= _DENSE_WORDS_CAP:
            return "dense"
        if self.use_pallas_kernels and self.fused_threshold is not None \
                and density > self.fused_threshold:
            return "fused"
        if self.use_pallas_kernels \
                and density > self.pallas_threshold:
            return "pallas"
        return "binary"

    # -- executor / stats plumbing --------------------------------------------

    def _make_executor(self, source=None) -> StreamingExecutor:
        # total resident slice words of the parallel window are bounded by
        # window-size × per-box budget (each planned slice is itself under
        # mem_words, modulo pinned spill rows)
        inflight_words = self.inflight_boxes * self.mem_words \
            if self.mem_words is not None else None
        return StreamingExecutor(self.source if source is None else source,
                                 pick_backend=self._pick_backend,
                                 chunk=self.chunk,
                                 prefetch_depth=self.prefetch_depth,
                                 use_pallas_kernels=self.use_pallas_kernels,
                                 dense_words_cap=_DENSE_WORDS_CAP,
                                 stats=self.stats,
                                 workers=self.workers,
                                 # store-backed binned layout lives in the
                                 # executor (per box slice); in-memory
                                 # engines keep the global binned path
                                 degree_bins=self.degree_bins
                                 and self.indices is None,
                                 inflight_boxes=self.inflight_boxes,
                                 inflight_words=inflight_words,
                                 tracer=self.tracer,
                                 metrics=self.metrics)

    def _reset_stats(self, n_boxes: int) -> None:
        self.stats = EngineStats(dense_threshold=self.dense_threshold,
                                 n_boxes=n_boxes,
                                 n_workers=self.workers,
                                 skew=self.skew,
                                 heavy_threshold=self._skew_threshold,
                                 source="edgestore" if self.indices is None
                                 else "memory")
        if self._box_lane:
            lanes = list(self._box_lane.values())
            self.stats.n_hub_boxes = lanes.count("hub")
            self.stats.n_light_boxes = lanes.count("light")
            self.stats.n_mixed_boxes = lanes.count("mixed")

    def _io_mark(self):
        cache = self._slice_cache
        cm = (cache.hits, cache.misses, cache.hit_words) if cache else None
        if self.device is None:
            return (None, cm)
        s = self.device.stats
        return ((s.block_reads, s.block_writes, s.word_reads), cm)

    def _io_collect(self, mark) -> None:
        io_mark, cm = mark
        if self.device is not None and io_mark is not None:
            s = self.device.stats
            self.stats.block_reads = s.block_reads - io_mark[0]
            self.stats.block_writes = s.block_writes - io_mark[1]
            self.stats.word_reads = s.word_reads - io_mark[2]
        if self._slice_cache is not None and cm is not None:
            cache = self._slice_cache
            self.stats.cache_hits = cache.hits - cm[0]
            self.stats.cache_misses = cache.misses - cm[1]
            self.stats.cache_hit_words = cache.hit_words - cm[2]

    # -- counting -------------------------------------------------------------

    def count(self) -> int:
        if self.tracer is not None:
            with self.tracer.span("engine.count", nv=self.nv,
                                  workers=self.workers):
                total = self._count_impl()
        else:
            total = self._count_impl()
        if self.metrics is not None:
            self.metrics.publish_stats(self.stats, "engine", mode="count")
        return total

    def _count_impl(self) -> int:
        boxes = self.plan()
        self._reset_stats(len(boxes))
        mark = self._io_mark()
        if not self.shard:
            ex = self._make_executor()
            if self.degree_bins and self.indices is not None:
                total = self._count_binned_boxes(boxes, ex)
            else:
                total = ex.run_count(boxes)
            self._io_collect(mark)
            return total
        # sharded: dense/pallas boxes run locally through the executor;
        # binary boxes are the data-parallel work-list. The neighbor stream
        # is staged through host memory once (see _staged_source).
        total = 0
        staged = self._staged_source()
        ex = self._make_executor(source=staged)
        sparse: List[Tuple[np.ndarray, np.ndarray]] = []
        sparse_boxes: List[Tuple[int, int, int, int]] = []
        heavy: List[Tuple[int, int, int, int]] = []
        with maybe_span(self.tracer, "shard.edges"):
            for box in boxes:
                eu, ev, wx, wy, slab = self._box_edges_full(box, staged)
                if len(eu) == 0:
                    continue
                be = self._pick_backend(len(eu), wx, wy, box)
                if be in ("dense", "pallas"):
                    if self.workers > 1 \
                            and getattr(staged, "device", None) is None:
                        # the local heavy boxes consume the same async
                        # queue as the non-sharded path; only when the
                        # staged source is uncharged (else the queue's
                        # fresh x-slab read would double-bill the DMA the
                        # slab reuse avoids)
                        heavy.append(box)
                    else:
                        total += ex.count_box(box, x_slab=slab)
                else:
                    sparse.append((eu, ev))
                    sparse_boxes.append(box)
                    self.stats.n_binary_boxes += 1
            if heavy:
                total += ex.run_count(heavy)
        if sparse:
            if self.degree_bins:
                total += self._count_sharded_binned(sparse, staged,
                                                    boxes=sparse_boxes)
            else:
                total += self._count_sharded(sparse, staged,
                                             boxes=sparse_boxes)
        self._io_collect(mark)
        return total

    def _count_binned_boxes(self, boxes, ex: StreamingExecutor) -> int:
        """Degree-binned single-host path: dense/pallas boxes stream through
        the executor; binary boxes concatenate into the per-bin-pair probe
        (padding waste is per-bin K, not global max degree)."""
        total = 0
        eus, evs = [], []
        for box in boxes:
            eu, ev, wx, wy, slab = self._box_edges_full(box)
            if len(eu) == 0:
                continue
            be = self._pick_backend(len(eu), wx, wy, box)
            if be in ("dense", "pallas"):
                total += ex.count_box(box, x_slab=slab)
            else:
                eus.append(eu)
                evs.append(ev)
                self.stats.n_binary_boxes += 1
        if eus:
            total += self._count_binned(np.concatenate(eus),
                                        np.concatenate(evs))
        return total

    def _count_binned(self, eu, ev) -> int:
        """Degree-binned count: gather per (bin_u, bin_v) pair, probe the
        narrower rows into the wider. Padding waste is per-bin K, not
        global max degree."""
        row_bin, bins = self.bins
        bin_pos = np.zeros(self.nv, dtype=np.int64)
        for rows, _ in bins:
            bin_pos[rows] = np.arange(len(rows))
        bu = row_bin[eu]
        bv = row_bin[ev]
        total = 0
        live = bv >= 0  # sink y-endpoints (out-degree 0) intersect empty
        for i, (_, npad_i) in enumerate(bins):
            for j, (_, npad_j) in enumerate(bins):
                sel = live & (bu == i) & (bv == j)
                if not sel.any():
                    continue
                a_rows = jnp.asarray(npad_i[bin_pos[eu[sel]]])
                b_rows = jnp.asarray(npad_j[bin_pos[ev[sel]]])
                total += int(_count_rows_chunked(a_rows, b_rows,
                                                 chunk=self.chunk))
        return total

    # -- sharded execution (the "Boxes" sharding rule) -------------------------

    def _schedule(self, edge_lists, boxes=None) -> list:
        """LPT shard schedule. The uniform planner balances on in-box edge
        counts; under ``skew="heavy_light"`` the cost is the box's actual
        *slice mass* (Σ member degrees via ``box_mass_costs``) — on skewed
        graphs a hub box's work is dominated by its neighbor mass, not its
        edge count, and edge-count LPT leaves workers idle behind it."""
        if boxes is not None and self.skew == "heavy_light":
            from repro.parallel.sharding import box_mass_costs
            return balanced_box_schedule(
                box_mass_costs(self.indptr, boxes), len(self.devices))
        return balanced_box_schedule([len(eu) for eu, _ in edge_lists],
                                     len(self.devices))

    def _gather(self, rows: np.ndarray, source=None) -> Tuple[np.ndarray,
                                                              np.ndarray]:
        """(deg, concat neighbor values) for sorted global rows, reading
        contiguous runs from the source (charged when store-backed)."""
        src = self.source if source is None else source
        if len(rows) == 0:
            return np.zeros(0, np.int64), np.zeros(0, np.int32)
        splits = np.flatnonzero(np.diff(rows) > 1) + 1
        degs, vals = [], []
        for run in np.split(rows, splits):
            ip, v = src.read_rows(int(run[0]), int(run[-1]))
            # runs are consecutive ids, so every row in [run0, run-1] is ours
            degs.append(np.diff(ip))
            vals.append(v)
        return np.concatenate(degs), np.concatenate(vals)

    def _shard_slices(self, edge_lists, schedule, pad_multiple, source=None):
        out = shard_local_slices(edge_lists, schedule,
                                 lambda rows: self._gather(rows, source),
                                 pad_multiple=pad_multiple)
        eu_s, ev_s, ok_s, npad_s, rows_s = out
        self.stats.n_shards = len(self.devices)
        self.stats.shard_edges = [int(x) for x in ok_s.sum(axis=1)]
        self.stats.shard_rows = [int((r >= 0).sum()) for r in rows_s]
        self.stats.local_npad_shape = tuple(npad_s.shape)
        return eu_s, ev_s, ok_s, npad_s, rows_s

    def _count_sharded(self, edge_lists, source=None, boxes=None) -> int:
        """Data-parallel box execution with *non-replicated* neighbor data:
        every shard receives only the renumbered rows its boxes touch, so
        per-device memory is O(slice), not O(V·K)."""
        with maybe_span(self.tracer, "shard.slices"):
            schedule = self._schedule(edge_lists, boxes=boxes)
            eu_s, ev_s, ok_s, npad_s, _rows = self._shard_slices(
                edge_lists, schedule, pad_multiple=self.chunk,
                source=source)
        with maybe_span(self.tracer, "shard.put"):
            args = [jnp.asarray(x) for x in (npad_s, eu_s, ev_s, ok_s)]
        with maybe_span(self.tracer, "shard.run"):
            run = sharded_count_program(box_mesh(self.devices), self.chunk)
            # int32 per shard on the device, summed exactly on the host
            return int(np.asarray(run(*args), dtype=np.int64).sum())

    def _binned_layout(self, source=None):
        """(row_bin, bins, bin_pos) for the sharded binned kernels.

        In-memory engines use the cached global layout; store-backed
        engines build it from the already-staged source CSR (the sharded
        paths stage the neighbor stream through host memory anyway), so
        ``degree_bins=True`` works sharded for both — never dropped.
        """
        if self.indices is not None:
            row_bin, bins = self.bins
        else:
            src = self.source if source is None else source
            row_bin, bins = pad_neighbors_binned(
                np.asarray(src.indptr), np.asarray(src.indices))
        bin_pos = np.zeros(self.nv, dtype=np.int64)
        for rows, _ in bins:
            bin_pos[rows] = np.arange(len(rows))
        return row_bin, bins, bin_pos

    def _count_sharded_binned(self, edge_lists, source=None,
                              boxes=None) -> int:
        """Sharded count through the degree-binned layout: one kernel per
        (bin_u, bin_v) width pair, each shard holding only the bin rows its
        edges reference. This wires ``pad_neighbors_binned`` into the
        shard_map path — a hub row no longer sets the padded width of every
        device array."""
        row_bin, bins, bin_pos = self._binned_layout(source)
        mesh = box_mesh(self.devices)
        schedule = self._schedule(edge_lists, boxes=boxes)
        n_shards = len(schedule)
        per_shard = []
        for boxes in schedule:
            if boxes:
                eu = np.concatenate([edge_lists[b][0] for b in boxes])
                ev = np.concatenate([edge_lists[b][1] for b in boxes])
            else:
                eu = ev = np.zeros(0, np.int64)
            per_shard.append((eu, ev))
        self.stats.n_shards = n_shards
        self.stats.shard_edges = [len(eu) for eu, _ in per_shard]

        pairs = set()
        for eu, ev in per_shard:
            if len(eu):
                live = row_bin[ev] >= 0
                pairs |= set(zip(row_bin[eu[live]].tolist(),
                                 row_bin[ev[live]].tolist()))
        total = 0
        chunk = self.chunk

        # one function object for every bin pair: jit keys retraces on the
        # (ka, kb, ra, rb, L) shapes, so pairs sharing shapes share a trace
        @jax.jit
        @partial(shard_map, mesh=mesh,
                 in_specs=(P("boxes", None, None), P("boxes", None, None),
                           P("boxes", None), P("boxes", None),
                           P("boxes", None)),
                 out_specs=P("boxes"), check_rep=False)
        def sharded_count_binned(npa, npb, eu, ev, ok):
            npa, npb = npa[0], npb[0]
            n_chunks = eu.shape[1] // chunk

            def body(carry, inp):
                u, v, valid = inp
                hit = _gather_hits(npa, npb, u, v)[1] \
                    & (valid[:, None] > 0)
                return carry + jnp.sum(hit.astype(jnp.int32)), None

            t, _ = jax.lax.scan(
                body, jnp.int32(0),
                (eu.reshape(n_chunks, chunk),
                 ev.reshape(n_chunks, chunk),
                 ok.reshape(n_chunks, chunk)))
            return t.reshape(1)

        for (i, j) in sorted(pairs):
            npa_i, npb_j = bins[i][1], bins[j][1]
            shard_data = []
            for eu, ev in per_shard:
                if len(eu) == 0:
                    shard_data.append((np.zeros(0, np.int64),) * 4)
                    continue
                sel = (row_bin[eu] == i) & (row_bin[ev] == j)
                eu_s, ev_s = eu[sel], ev[sel]
                ur = np.unique(eu_s)
                vr = np.unique(ev_s)
                shard_data.append((eu_s, ev_s, ur, vr))
            ra = max([len(d[2]) for d in shard_data] + [0]) + 1
            rb = max([len(d[3]) for d in shard_data] + [0]) + 1
            lmax = max([len(d[0]) for d in shard_data] + [1])
            L = -(-lmax // chunk) * chunk
            ka, kb = npa_i.shape[1], npb_j.shape[1]
            npa = np.full((n_shards, ra, ka), SENTINEL, np.int32)
            npb = np.full((n_shards, rb, kb), SENTINEL, np.int32)
            eu_l = np.full((n_shards, L), ra - 1, np.int32)
            ev_l = np.full((n_shards, L), rb - 1, np.int32)
            ok_l = np.zeros((n_shards, L), np.int32)
            for s, (eu_s, ev_s, ur, vr) in enumerate(shard_data):
                if len(eu_s) == 0:
                    continue
                npa[s, :len(ur)] = npa_i[bin_pos[ur]]
                npb[s, :len(vr)] = npb_j[bin_pos[vr]]
                eu_l[s, :len(eu_s)] = np.searchsorted(ur, eu_s)
                ev_l[s, :len(ev_s)] = np.searchsorted(vr, ev_s)
                ok_l[s, :len(eu_s)] = 1

            parts = sharded_count_binned(
                jnp.asarray(npa), jnp.asarray(npb), jnp.asarray(eu_l),
                jnp.asarray(ev_l), jnp.asarray(ok_l))
            total += int(jnp.sum(parts))
        return total

    # -- listing --------------------------------------------------------------

    def list(self, capacity: Optional[int] = None) -> np.ndarray:
        """Enumerate all triangles; returns canonical sorted (m, 3) rows.

        Each row is ``(min, mid, max)`` and the rows are in lexicographic
        order, as int64 (``_canonical``). One device pass per box lists
        it exactly (``run_list``). The sharded path's per-shard output
        buffer is bounded (``capacity`` triangles); because its kernels
        return the *exact* total alongside the buffer, overflow is
        detected and resolved by rescanning with the capacity doubled
        until everything fits (counting is cheap relative to
        materialization, so a rescan costs one extra pass).
        """
        if self.tracer is not None:
            with self.tracer.span("engine.list", nv=self.nv,
                                  workers=self.workers):
                tris = self._list_impl(capacity)
        else:
            tris = self._list_impl(capacity)
        if self.metrics is not None:
            self.metrics.publish_stats(self.stats, "engine", mode="list")
        return tris

    def _list_impl(self, capacity: Optional[int] = None) -> np.ndarray:
        return self._canonical(self._list_rows(capacity))

    def _list_rows(self, capacity: Optional[int] = None) -> np.ndarray:
        """Every triangle once, in no fixed order or orientation."""
        boxes = self.plan()
        self._reset_stats(len(boxes))
        mark = self._io_mark()
        if not self.shard:
            ex = self._make_executor()
            tris = ex.run_list(boxes)
            self._io_collect(mark)
            return tris
        staged = self._staged_source()
        edge_lists = []
        kept_boxes = []
        for box in boxes:
            eu, ev, _, _ = self._box_edges(box, staged)
            if len(eu):
                edge_lists.append((eu, ev))
                kept_boxes.append(box)
        if not edge_lists:
            return np.zeros((0, 3), dtype=np.int64)
        if capacity is None:
            m = sum(len(eu) for eu, _ in edge_lists)
            capacity = max(256, m)
        cap = _pow2(max(2, capacity))
        if self.degree_bins:
            # binned sharded listing: per-bin-pair enumeration kernel on
            # the binned widths (same counts/rows as the unbinned kernel,
            # padding bounded by the bin growth factor — no fallback)
            tris = self._list_sharded_binned(edge_lists, cap, staged,
                                             boxes=kept_boxes)
            self._io_collect(mark)
            return tris
        # the shard slices are identical across capacity rescans: build
        # (and charge) them once, re-run only the kernel on overflow
        mesh = box_mesh(self.devices)
        chunk = min(self.chunk, 1024)
        slices = self._shard_slices(edge_lists,
                                    self._schedule(edge_lists,
                                                   boxes=kept_boxes),
                                    pad_multiple=chunk, source=staged)
        while True:
            tris, ok = self._list_sharded(slices, cap, mesh, chunk)
            if ok:
                break
            self.stats.n_rescans += 1
            cap *= 2
        self._io_collect(mark)
        return tris

    def _canonical(self, tris: np.ndarray) -> np.ndarray:
        """``tris`` (ids below ``nv``, any row orientation or order) as
        ``(min, mid, max)`` int64 rows in lexicographic order.

        While the id width ``bits = (nv - 1).bit_length()`` is at most 21,
        three ids fill at most 63 bits, the non-negative range of one
        int64 key, and a single in-place sort of the keys orders the rows
        (``_sorted_packed_rows``): no row sort, argsort or row gather.
        Wider ids (``nv > 2**21``) take ``np.lexsort`` over the row-sorted
        columns, counted in ``stats.n_canonical_fallbacks``."""
        m = len(tris)
        bits = max(1, (self.nv - 1).bit_length())
        packed = 3 * bits <= 63
        with maybe_span(self.tracer, "engine.canonical", rows=m,
                        packed=packed):
            if m == 0:
                return np.zeros((0, 3), dtype=np.int64)
            if packed:
                return _sorted_packed_rows(np.asarray(tris), bits)
            self.stats.n_canonical_fallbacks += 1
            tris = np.sort(np.asarray(tris, dtype=np.int64), axis=1)
            order = np.lexsort((tris[:, 2], tris[:, 1], tris[:, 0]))
            return tris[order]

    def _list_sharded(self, slices, cap: int, mesh, chunk: int):
        eu_s, ev_s, ok_s, npad_s, rows_s = slices

        @partial(jax.jit, static_argnames=())
        @partial(shard_map, mesh=mesh,
                 in_specs=(P("boxes", None, None), P("boxes", None),
                           P("boxes", None), P("boxes", None)),
                 out_specs=(P("boxes"), P("boxes", None, None)),
                 check_rep=False)
        def sharded_list(npad, eu, ev, ok):
            total, buf = _list_chunked(npad[0], eu[0], ev[0],
                                       cap=cap, chunk=chunk, valid=ok[0])
            return total.reshape(1), buf.reshape(1, cap, 3)

        totals, bufs = sharded_list(jnp.asarray(npad_s), jnp.asarray(eu_s),
                                    jnp.asarray(ev_s), jnp.asarray(ok_s))
        totals = np.asarray(totals)
        if (totals > cap).any():
            return None, False
        bufs = np.asarray(bufs)
        parts = []
        for s in range(len(totals)):
            t = bufs[s, :totals[s]].astype(np.int64)
            if len(t) == 0:
                continue
            t[:, 0] = rows_s[s][t[:, 0]]   # local row ids -> global vertices
            t[:, 1] = rows_s[s][t[:, 1]]
            parts.append(t)
        tris = np.concatenate(parts) if parts \
            else np.zeros((0, 3), np.int64)
        if self.device is not None:
            self.device.write_words(3 * len(tris))
        return tris, True

    def _list_sharded_binned(self, edge_lists, cap: int, source=None,
                             boxes=None) -> np.ndarray:
        """Sharded listing through the degree-binned layout (the listing
        analogue of ``_count_sharded_binned``): one ``_list_pairs_chunked``
        launch per (bin_u, bin_v) width pair, each shard holding only the
        bin rows its edges reference. The kernel emits *global* (u, v, z)
        triangles directly, so no local-row remap is needed; per-pair
        overflow rescans that pair at doubled capacity."""
        row_bin, bins, bin_pos = self._binned_layout(source)
        mesh = box_mesh(self.devices)
        schedule = self._schedule(edge_lists, boxes=boxes)
        n_shards = len(schedule)
        per_shard = []
        for shard_boxes in schedule:
            if shard_boxes:
                eu = np.concatenate([edge_lists[b][0] for b in shard_boxes])
                ev = np.concatenate([edge_lists[b][1] for b in shard_boxes])
            else:
                eu = ev = np.zeros(0, np.int64)
            per_shard.append((eu, ev))
        self.stats.n_shards = n_shards
        self.stats.shard_edges = [len(eu) for eu, _ in per_shard]

        pairs = set()
        for eu, ev in per_shard:
            if len(eu):
                live = (row_bin[eu] >= 0) & (row_bin[ev] >= 0)
                pairs |= set(zip(row_bin[eu[live]].tolist(),
                                 row_bin[ev[live]].tolist()))
        chunk = min(self.chunk, 1024)
        parts: List[np.ndarray] = []

        def launch(npa, npb, eu_l, ev_l, us_l, vs_l, cap_):
            @jax.jit
            @partial(shard_map, mesh=mesh,
                     in_specs=(P("boxes", None, None),
                               P("boxes", None, None),
                               P("boxes", None), P("boxes", None),
                               P("boxes", None), P("boxes", None)),
                     out_specs=(P("boxes"), P("boxes", None, None)),
                     check_rep=False)
            def sharded_list_binned(npa, npb, eu, ev, us, vs):
                total, buf = _list_pairs_chunked(
                    npa[0], npb[0], eu[0], ev[0], us[0], vs[0],
                    cap=cap_, chunk=chunk)
                return total.reshape(1), buf.reshape(1, cap_, 3)

            return sharded_list_binned(
                jnp.asarray(npa), jnp.asarray(npb), jnp.asarray(eu_l),
                jnp.asarray(ev_l), jnp.asarray(us_l), jnp.asarray(vs_l))

        for (i, j) in sorted(pairs):
            npa_i, npb_j = bins[i][1], bins[j][1]
            shard_data = []
            for eu, ev in per_shard:
                if len(eu) == 0:
                    shard_data.append((np.zeros(0, np.int64),) * 4)
                    continue
                sel = (row_bin[eu] == i) & (row_bin[ev] == j)
                eu_s, ev_s = eu[sel], ev[sel]
                ur = np.unique(eu_s)
                vr = np.unique(ev_s)
                shard_data.append((eu_s, ev_s, ur, vr))
            # one all-SENTINEL pad row on BOTH sides: the kernel may swap
            # the matrices (narrower probes wider) and its pad slots — and
            # ours — must land on an empty row either way
            ra = max([len(d[2]) for d in shard_data] + [0]) + 1
            rb = max([len(d[3]) for d in shard_data] + [0]) + 1
            lmax = max([len(d[0]) for d in shard_data] + [1])
            L = -(-lmax // chunk) * chunk
            ka, kb = npa_i.shape[1], npb_j.shape[1]
            npa = np.full((n_shards, ra, ka), SENTINEL, np.int32)
            npb = np.full((n_shards, rb, kb), SENTINEL, np.int32)
            eu_l = np.full((n_shards, L), ra - 1, np.int32)
            ev_l = np.full((n_shards, L), rb - 1, np.int32)
            us_l = np.zeros((n_shards, L), np.int32)
            vs_l = np.zeros((n_shards, L), np.int32)
            for s, (eu_s, ev_s, ur, vr) in enumerate(shard_data):
                if len(eu_s) == 0:
                    continue
                npa[s, :len(ur)] = npa_i[bin_pos[ur]]
                npb[s, :len(vr)] = npb_j[bin_pos[vr]]
                eu_l[s, :len(eu_s)] = np.searchsorted(ur, eu_s)
                ev_l[s, :len(ev_s)] = np.searchsorted(vr, ev_s)
                us_l[s, :len(eu_s)] = eu_s
                vs_l[s, :len(ev_s)] = ev_s
            cap_p = cap
            while True:
                totals, bufs = launch(npa, npb, eu_l, ev_l, us_l, vs_l,
                                      cap_p)
                totals = np.asarray(totals)
                if not (totals > cap_p).any():
                    break
                self.stats.n_rescans += 1
                cap_p *= 2
            bufs = np.asarray(bufs)
            for s in range(len(totals)):
                if totals[s]:
                    parts.append(bufs[s, :totals[s]].astype(np.int64))
        tris = np.concatenate(parts) if parts \
            else np.zeros((0, 3), np.int64)
        if self.device is not None:
            self.device.write_words(3 * len(tris))
        return tris


# ---------------------------------------------------------------------------
# module-level conveniences
# ---------------------------------------------------------------------------

def engine_count(src, dst, **kw) -> int:
    return TriangleEngine(src, dst, **kw).count()


def engine_list(src, dst, **kw) -> np.ndarray:
    return TriangleEngine(src, dst, **kw).list()
