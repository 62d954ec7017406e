"""Benchmark harness: one module per paper table/figure (DESIGN.md §6).

  fig7   boxing CPU overhead + box counts      (benchmarks.boxing_overhead)
  fig9   vanilla vs boxed block I/Os + Prop.4  (benchmarks.vanilla_vs_boxed)
  fig11  boxed LFTJ vs specialized MGT         (benchmarks.lftj_vs_mgt)
  thm17  arboricity scaling of LFTJ-Δ          (benchmarks.arboricity_scaling)
  ooc    out-of-core engine I/O vs Thm. 10     (benchmarks.outofcore)
  query  general patterns I/O vs Thm. 13       (benchmarks.query_patterns)
  pscale async scheduler speedup vs workers    (benchmarks.parallel_scaling)
  skew   heavy/light vs uniform planner A/B    (benchmarks.skew_scaling)
  kernels Pallas kernels vs references          (benchmarks.kernel_bench)
  roofline per-cell roofline terms from dry-run (benchmarks.roofline)
  serve   concurrent serving latency + envelope (benchmarks.serve_load)
  fabric  distributed box-fabric shard scaling  (benchmarks.fabric_scaling)

Prints ``name,us_per_call,derived`` CSV. ``--fast`` shrinks sizes;
``--only fig9`` runs a single suite; ``--smoke`` is the CI gate — the
cheapest suite subset at fast sizes, exercising the engine + I/O model
(including the mmap edge store) end to end. ``--json PATH`` additionally
writes the emitted rows as JSON (CI uploads it as a build artifact so the
perf trajectory is tracked per PR).

``--summary PATH`` writes a consolidated ``bench_summary.json``: one
record per suite with its name, wall seconds, the gate rows it emitted,
and a snapshot of the process-wide metrics registry (``repro.obs``) —
the ``box.*`` queue telemetry every engine run folds into the default
registry while a suite executes.
"""

from __future__ import annotations

import argparse
import json
import platform
import sys
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true")
    ap.add_argument("--only", default=None)
    ap.add_argument("--smoke", action="store_true",
                    help="CI smoke pass: fig9 + fig11 + ooc + query + skew "
                         "+ serve at --fast sizes")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write emitted rows as a JSON run record")
    ap.add_argument("--summary", default=None, metavar="PATH",
                    help="write a consolidated per-suite summary (name, "
                         "wall seconds, gate rows, metrics-registry "
                         "snapshot) as JSON")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.smoke:
        args.fast = True

    from . import (arboricity_scaling, boxing_overhead, fabric_scaling,
                   kernel_bench, lftj_vs_mgt, outofcore, parallel_scaling,
                   query_patterns, roofline, serve_load, skew_scaling,
                   vanilla_vs_boxed)
    from .common import collected_rows, reset_rows

    suites = {
        "fig7": boxing_overhead.main,
        "fig9": vanilla_vs_boxed.main,
        "fig11": lftj_vs_mgt.main,
        "thm17": arboricity_scaling.main,
        "ooc": outofcore.main,
        "query": query_patterns.main,
        "pscale": parallel_scaling.main,
        "skew": skew_scaling.main,
        "kernels": kernel_bench.main,
        "roofline": roofline.main,
        "serve": serve_load.main,
        "fabric": fabric_scaling.main,
    }
    if args.only:
        names = [args.only]
    elif args.smoke:
        names = ["fig9", "fig11", "ooc", "query", "skew", "serve"]
    else:
        names = list(suites)
    reset_rows()
    timings = {}
    summary = []
    print("name,us_per_call,derived")
    for n in names:
        # one fresh default registry per suite: instrumented code the
        # suite constructs (engines, servers) folds its queue telemetry
        # into it without any benchmark signature changing
        reg = None
        if args.summary:
            from repro.obs import MetricsRegistry, set_default_registry
            reg = MetricsRegistry()
            set_default_registry(reg)
        rows_before = len(collected_rows())
        t0 = time.time()
        print(f"# --- {n} ---", flush=True)
        suites[n](fast=args.fast)
        timings[n] = time.time() - t0
        print(f"# {n} done in {timings[n]:.1f}s", flush=True)
        if reg is not None:
            from repro.obs import set_default_registry
            set_default_registry(None)
            summary.append({
                "name": n,
                "wall_s": round(timings[n], 3),
                "rows": collected_rows()[rows_before:],
                "metrics": reg.snapshot(),
            })
    if args.summary:
        with open(args.summary, "w") as f:
            json.dump({"suites": summary, "fast": bool(args.fast),
                       "python": platform.python_version()}, f, indent=2)
        print(f"# wrote {args.summary} ({len(summary)} suites)",
              flush=True)
    if args.json:
        record = {
            "suites": names,
            "fast": bool(args.fast),
            "python": platform.python_version(),
            "suite_seconds": {k: round(v, 2) for k, v in timings.items()},
            "rows": collected_rows(),
        }
        with open(args.json, "w") as f:
            json.dump(record, f, indent=2)
        print(f"# wrote {args.json} ({len(record['rows'])} rows)",
              flush=True)


if __name__ == '__main__':
    main()
