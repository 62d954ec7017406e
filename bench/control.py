#!/usr/bin/env python3
"""The control of the comparison that decides ``correct``: the cell run
with the configuration's guarantee of an exact answer broken, which the
comparison has to refuse.

The control puts an approximate triangle count in the program's place:
DOULION sparsification (Tsourakakis et al., KDD 2009). Each edge is kept
with probability ``P_KEEP`` before ingest, and every count is scaled by
1 / ``P_KEEP``³; a listing lists the triangles of the sparsified graph.
This is the step that would tempt a later change, since it cuts the work
by a factor of eight. The benchmark's own runs never take this path.

    python3 bench/control.py --workload g500-s18.count --seeds 21 22 23 \
        --seconds 1

runs the control on the chip, one seed after another in one process, and
prints each run's numbers beside their limits.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import sys
import time
from pathlib import Path

import numpy as np

T0 = time.perf_counter()
ROOT = Path(__file__).resolve().parents[1]
P_KEEP = 0.5
SPARSIFY_SEED = 20090628


@contextlib.contextmanager
def approximate_engine(p: float = P_KEEP):
    """Within the block ``TriangleEngine`` ingests a sparsified graph and
    scales its counts; the reference still sees every edge."""
    from repro.core.engine import TriangleEngine

    ingest, count = TriangleEngine.ingest, TriangleEngine.count

    def sparse_ingest(cls, path, edges, **kw):
        src, dst = edges
        keep = np.random.default_rng(SPARSIFY_SEED).random(len(src)) < p
        return ingest.__func__(cls, path, (src[keep], dst[keep]), **kw)

    def scaled_count(self):
        return int(round(count(self) / p ** 3))

    TriangleEngine.ingest = classmethod(sparse_ingest)
    TriangleEngine.count = scaled_count
    try:
        yield
    finally:
        TriangleEngine.ingest, TriangleEngine.count = ingest, count


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=1.0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax

    from bench.compile_cache import enable_compile_cache
    from bench.harness import Spec, run_cell

    spec = Spec(ROOT)
    devices = jax.devices()
    if devices[0].platform != "tpu" \
            or len(devices) < spec.workload(args.workload)["chips"]:
        print("control: no TPU, or too few chips", file=sys.stderr)
        return 3
    enable_compile_cache()
    for seed in args.seeds:
        with approximate_engine():
            res = run_cell(spec, args.workload, seed, args.seconds, False,
                           devices, time.perf_counter())
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "correct": res.correct, "checks": res.checks}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
