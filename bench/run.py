#!/usr/bin/env python3
"""Run one benchmark cell once on the chip and print its result.

    python3 bench/run.py --workload g500-s18.count --seed 7 --seconds 30 \
        --trace 0

The cell (``workloads`` in ``BENCHMARK.json``) names a configuration, a
traffic mix and a number of chips. The run builds the configuration's
graph from ``--seed``, ingests it into an edge store through
``TriangleEngine.ingest``, warms up, then runs the mix's passes back to
back for ``--seconds`` and checks every answer against the plain
reference. With ``--trace 0`` the result holds the cell's end-to-end
metrics; with ``--trace 1`` the window runs under the profiler and the
program's span recorder, and the result holds the per-layer metrics,
the device's busy time and a breakdown.

The last line of standard output is the result as one JSON object; the
last lines of standard error are the numbers compared, each beside its
limit. Without a TPU, or with fewer chips than the cell asks for, the
run exits non-zero and prints no result.
"""

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def fail(msg: str, code: int) -> int:
    print(f"bench: {msg}; no result", file=sys.stderr, flush=True)
    return code


def main(argv=None) -> int:
    args = parse_args(argv)
    # libtpu's logs stay inside the checkout
    if "TPU_LOG_DIR" not in os.environ:
        logs = ROOT / ".bench" / "tpu_logs"
        logs.mkdir(parents=True, exist_ok=True)
        os.environ["TPU_LOG_DIR"] = str(logs)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench.harness import Spec, run_cell

    try:
        spec = Spec(ROOT)
        chips = spec.workload(args.workload)["chips"]
    except (OSError, KeyError, ValueError) as e:
        return fail(str(e), 2)
    try:
        import repro  # noqa: F401
    except ImportError:
        return fail("the program (src/repro) is not in this checkout", 2)
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        return fail(f"no TPU (JAX sees {devices[0].platform})", 3)
    if len(devices) < chips:
        return fail(f"{chips} chips asked for, {len(devices)} present", 3)
    from bench.compile_cache import enable_compile_cache

    enable_compile_cache()
    res = run_cell(spec, args.workload, args.seed, args.seconds,
                   bool(args.trace), devices, T0)
    for name, c in res.checks.items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(res.line(), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
