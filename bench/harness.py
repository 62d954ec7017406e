"""The benchmark harness: finds a cell's configuration, generator,
traffic mix, entry and per-layer metrics by name, sets the cell up,
times its window, checks every answer against the plain reference and
reduces the traced run.

Everything that belongs to one of these lives in a file of its own,
found by the name ``BENCHMARK.json`` or a configuration gives it:

- ``bench/configs/<config>.json`` (the path is the entry's ``file``):
  the graph's generator and sizes, the store layout, the memory budget
  and the guarantees;
- ``bench/graphs/<generator>.py``, named by the configuration's
  ``generator``: ``edges(cfg)`` gives the raw edges and vertex count;
- ``bench/traffic/<traffic>.json``: the mix's parameters: which entry
  one pass is, the end-to-end metric it reports, the warm-up passes and
  any engine options;
- ``bench/entries/<entry>.py``, named by the mix's ``entry``: what one
  pass is and how its answers are checked. ``open(setup)`` builds what
  the passes drive from a ``Setup``, ``run(state)`` is one pass, and
  ``check(results, dag)`` compares every pass's answer with the plain
  reference and returns ``({name: (value, limit)}, failed passes)``;
- ``bench/metrics/<metric>.py``: a reader ``read(run)`` that returns the
  metric from the traced run, or None where there is nothing to read.

A new configuration, generator, mix, entry or metric is a new file and
an entry in ``BENCHMARK.json``; no file of the harness changes.
"""

from __future__ import annotations

import gc
import importlib.util
import json
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from bench import reference, spans as span_ops

ROOT = Path(__file__).resolve().parents[1]
WORKDIR = ".bench"                   # inside the checkout, git-ignored
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


class SpecError(ValueError):
    """BENCHMARK.json or a file it names is missing or malformed."""


# ---------------------------------------------------------------------------
# the specification and the files it names
# ---------------------------------------------------------------------------

class Spec:
    """``BENCHMARK.json`` of the checkout at ``root`` and the files of its
    configurations, traffic mixes and metrics."""

    def __init__(self, root: Path = ROOT):
        self.root = Path(root)
        self.bench = self.root / "bench"
        path = self.root / "BENCHMARK.json"
        if not path.is_file():
            raise SpecError(f"no BENCHMARK.json in {self.root}")
        self.doc = json.loads(path.read_text())

    def _entry(self, key: str, name: str) -> dict:
        for e in self.doc[key]:
            if e["name"] == name:
                return e
        raise SpecError(f"no {key} entry named {name!r}")

    def workload(self, name: str) -> dict:
        return self._entry("workloads", name)

    def config(self, name: str) -> dict:
        path = self.root / self._entry("configs", name)["file"]
        return json.loads(path.read_text())

    def traffic(self, name: str) -> dict:
        path = self.bench / "traffic" / f"{name}.json"
        if not path.is_file():
            raise SpecError(f"no traffic file {path}")
        return json.loads(path.read_text())

    def _module(self, kind: str, name: str, what: str):
        """The module of ``bench/<kind>/<name>.py``."""
        path = self.bench / kind / f"{name}.py"
        if not path.is_file():
            raise SpecError(f"no {what} {path}")
        mod_name = f"bench_{kind}_" + "".join(
            c if c.isalnum() else "_" for c in name)
        spec = importlib.util.spec_from_file_location(mod_name, path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def metric_reader(self, name: str):
        return self._module("metrics", name, "metric reader")

    def entry(self, name: str):
        return self._module("entries", name, "entry")

    def generator(self, name: str):
        return self._module("graphs", name, "generator")

    def make_graph(self, cfg: dict, seed: int):
        """The configuration's raw edges with vertex labels permuted from
        ``seed``: every seed gets the same graph up to isomorphism (the
        same triangles, degrees and work) under other vertex ids."""
        (src, dst), n = self.generator(cfg["generator"]).edges(cfg)
        perm = np.random.default_rng(seed).permutation(n)
        return perm[src], perm[dst]

    def _reports(self, metric: dict, workload: str,
                 e2e: Sequence[str]) -> bool:
        if "workloads" in metric:
            return workload in metric["workloads"]
        return metric.get("moves") is None or metric["moves"] in e2e

    def end_to_end(self, workload: str) -> List[dict]:
        return [m for m in self.doc["end_to_end"]
                if self._reports(m, workload, ())]

    def per_layer(self, workload: str) -> List[dict]:
        e2e = [m["name"] for m in self.end_to_end(workload)]
        return [m for m in self.doc["per_layer"]
                if self._reports(m, workload, e2e)]


def peaks_for(kind: str) -> dict:
    """Published peaks of one chip of ``device_kind`` ``kind``
    (``bench/peaks.json``); a kind the table does not hold is an error,
    never a default."""
    path = Path(__file__).resolve().parent / "peaks.json"
    table = json.loads(path.read_text())["devices"]
    if kind not in table:
        raise KeyError(f"no published peaks for device kind {kind!r} "
                       f"(known: {sorted(table)})")
    return table[kind]


@dataclass
class Setup:
    """What an entry's ``open`` builds its passes from: the run's raw
    edges, the configuration, the mix's engine options, the devices and
    the span recorder (None in an untraced run)."""
    store_path: Path
    src: np.ndarray
    dst: np.ndarray
    cfg: dict
    options: dict
    devices: list
    tracer: Any

    def triangle_engine(self):
        """The configuration's edge store, built by the program's own
        ``TriangleEngine.ingest`` as a user would build it."""
        from repro.core.engine import TriangleEngine

        store = self.cfg["store"]
        eng = TriangleEngine.ingest(
            self.store_path, (self.src, self.dst),
            orientation=store["orientation"],
            chunk_rows=store["chunk_rows"],
            ingest_budget_words=store["ingest_budget_words"],
            mem_words=self.cfg["mem_words"], devices=self.devices,
            tracer=self.tracer, **self.options)
        _log(f"ingested {eng.source.n_edges} edges over {eng.nv} "
             f"vertices, {len(eng.plan())} boxes")
        return eng


# ---------------------------------------------------------------------------
# the measured window
# ---------------------------------------------------------------------------

@dataclass
class Window:
    start: float                  # clock reading at the first pass
    ends: List[float]             # seconds from start at each pass's end
    results: List[Any]

    @property
    def seconds(self) -> float:
        return self.ends[-1]

    @property
    def per_pass(self) -> float:
        return self.ends[-1] / len(self.ends)


def run_window(one_pass: Callable[[], Any], seconds: float,
               clock: Callable[[], float] = time.perf_counter) -> Window:
    """Passes back to back; the window ends with the first pass that ends
    at or after ``seconds``, so it holds at least one whole pass."""
    start = clock()
    ends, results = [], []
    while True:
        results.append(one_pass())
        ends.append(clock() - start)
        if ends[-1] >= seconds:
            return Window(start, ends, results)


class CompileCounter:
    """Programs JAX compiled or loaded from its persistent cache, with
    the clock reading at each (JAX's own ``backend_compile`` events)."""

    def __init__(self, clock: Callable[[], float] = time.perf_counter):
        import jax

        self.clock = clock
        self.times: List[float] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event == BACKEND_COMPILE:
            self.times.append(self.clock())

    def between(self, lo: float, hi: float) -> int:
        return sum(lo <= t <= hi for t in self.times)


# ---------------------------------------------------------------------------
# what the metric readers see
# ---------------------------------------------------------------------------

@dataclass
class RunView:
    """The traced run as the per-layer readers see it."""
    passes: int
    spans: List[span_ops.Span]          # program spans inside the window
    stats: Any                          # EngineStats of the last pass
    compiles: int                       # programs compiled in the window
    trace: Any                          # trace_reduce.TraceSummary
    graph_words: int                    # stored edges + vertices
    peaks: dict

    def per_pass(self, x: Optional[float]) -> Optional[float]:
        return None if x is None else x / self.passes


@dataclass
class Result:
    correct: bool
    attempted: int
    failed: int
    metrics: Dict[str, dict]
    device: Dict[str, Any]
    checks: Dict[str, dict]
    breakdown: Optional[dict] = None

    def line(self) -> str:
        out = {"correct": self.correct, "attempted": self.attempted,
               "failed": self.failed, "metrics": self.metrics,
               "device": self.device}
        if self.breakdown is not None:
            out["breakdown"] = self.breakdown
        out["checks"] = self.checks
        return json.dumps(out)


def _peak_bytes(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks, default=0))


def _traced_window(prof_dir: Path, tracer, one_pass, seconds: float,
                   devices) -> Tuple[Window, int]:
    """The window under ``jax.profiler`` (written to ``prof_dir``) and a
    ``bench.window`` span that puts the program's spans on the
    profiler's clock."""
    import jax

    from bench.trace_reduce import WINDOW

    shutil.rmtree(prof_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0           # no per-call host overhead
    jax.profiler.start_trace(str(prof_dir), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(WINDOW), tracer.span(WINDOW):
            window = run_window(one_pass, seconds)
        return window, _peak_bytes(devices)
    finally:
        jax.profiler.stop_trace()


def _log(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


def run_cell(spec: Spec, workload: str, seed: int, seconds: float,
             trace: bool, devices: Sequence, t0: float, *,
             select=None, op_filter=None) -> Result:
    """Set up, time and check one run of ``workload`` on ``devices``.
    ``t0`` is the clock reading at process start (``setup_s`` counts
    from it). ``select``/``op_filter`` choose the trace's device lines
    (default: the TPU's ``XLA Ops``)."""
    from repro.obs import Tracer

    wl = spec.workload(workload)
    cfg = spec.config(wl["config"])
    traffic = spec.traffic(wl["traffic"])
    entry = spec.entry(traffic["entry"])
    devices = list(devices)[:wl["chips"]]
    compiles = CompileCounter()

    work = spec.root / WORKDIR
    work.mkdir(parents=True, exist_ok=True)
    tmp = Path(tempfile.mkdtemp(prefix="run-", dir=work))
    try:
        _log(f"devices ready {time.perf_counter() - t0:.3f} s from start")
        src, dst = spec.make_graph(cfg, seed)
        _log(f"graph made {time.perf_counter() - t0:.3f} s from start")
        tracer = Tracer(capacity=1 << 20) if trace else None
        state = entry.open(Setup(tmp / "graph.store", src, dst, cfg,
                                 traffic.get("engine", {}), devices, tracer))
        _log(f"opened {time.perf_counter() - t0:.3f} s from start")
        for _ in range(traffic["warm_passes"]):
            entry.run(state)
        setup_s = time.perf_counter() - t0
        _log(f"setup_s {setup_s:.6f}")

        if trace:
            window, peak = _traced_window(work / "profile", tracer,
                                          lambda: entry.run(state), seconds,
                                          devices)
        else:
            window = run_window(lambda: entry.run(state), seconds)
            peak = _peak_bytes(devices)
        in_window = compiles.between(window.start,
                                     window.start + window.seconds)
        _log(f"window {window.seconds:.6f} s, {len(window.ends)} "
             f"passes ending at {[round(t, 3) for t in window.ends]} s, "
             f"{in_window} programs compiled in it")
        stats = getattr(state, "stats", None)
        del state
        gc.collect()

        t_ref = time.perf_counter()
        dag = reference.reference_dag(src, dst)
        del src, dst
        checks, failed = entry.check(window.results, dag)
        _log(f"reference and check {time.perf_counter() - t_ref:.3f} s")
        correct = all(v <= lim for v, lim in checks.values())
        graph_words = int(dag.nnz) + int(dag.shape[0])
        del dag

        d0 = devices[0]
        device = {"platform": d0.platform, "kind": d0.device_kind,
                  "count": len(devices), "memory_peak_bytes": peak}
        breakdown = None
        if trace:
            summary, view = _reduce_trace(
                spec, wl, tracer, window, stats, in_window, graph_words,
                devices, select, op_filter)
            device["busy_s"] = summary.busy_s
            device["window_s"] = summary.window_s
            breakdown = {"device_ops": [list(x) for x in summary.device_ops],
                         "idle_gaps": [list(x) for x in summary.idle_gaps]}
            metrics = {}
            for m in spec.per_layer(workload):
                value = spec.metric_reader(m["name"]).read(view)
                if value is not None:
                    metrics[m["name"]] = {"value": float(value),
                                          "unit": m["unit"]}
        else:
            values = {traffic["metric"]: window.per_pass,
                      "setup_s": setup_s}
            metrics = {m["name"]: {"value": values[m["name"]],
                                   "unit": m["unit"]}
                       for m in spec.end_to_end(workload)}
        return Result(correct=correct, attempted=len(window.results),
                      failed=failed, metrics=metrics, device=device,
                      checks={k: {"value": v, "limit": lim}
                              for k, (v, lim) in checks.items()},
                      breakdown=breakdown)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def _reduce_trace(spec, wl, tracer, window, stats, in_window, graph_words,
                  devices, select, op_filter):
    import jax

    from bench import trace_reduce

    prof_dir = spec.root / WORKDIR / "profile"
    all_spans = span_ops.spans_from_snapshot(tracer.snapshot())
    if tracer.dropped:
        raise RuntimeError(f"the span ring dropped {tracer.dropped} events")
    win = [s for s in all_spans if s.name == trace_reduce.WINDOW][0]
    inside = [s for s in all_spans
              if s.start >= win.start and s.end <= win.end and s is not win]
    profile = jax.profiler.ProfileData.from_file(
        trace_reduce.find_xplane(str(prof_dir)))
    lo, _ = trace_reduce.window_of(profile)
    shift = lo * 1e-9 - win.start          # tracer clock -> profiler clock
    host = [span_ops.Span(s.name, s.tid, s.start + shift, s.end + shift)
            for s in inside]
    kw = {}
    if select is not None:
        kw["select"] = select
    if op_filter is not None:
        kw["op_filter"] = op_filter
    summary = trace_reduce.reduce_profile(
        profile, devices=list(range(len(devices))), host_spans=host, **kw)
    shutil.rmtree(prof_dir, ignore_errors=True)
    view = RunView(passes=len(window.ends), spans=inside, stats=stats,
                   compiles=in_window, trace=summary, graph_words=graph_words,
                   peaks=peaks_for(devices[0].device_kind)
                   if devices[0].platform == "tpu" else {})
    return summary, view
