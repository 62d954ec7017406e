"""Helpers of the benchmark's CPU tests: a copy of the benchmark whose
configurations are cut to a size a test run holds."""

from __future__ import annotations

import json
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

# the XLA ops of the CPU backend, standing in for a TPU's ``XLA Ops`` line
CPU_OPS = dict(
    select=lambda plane, line: 0 if plane == "/host:CPU"
    and line.startswith("tf_XLAPjRtCpuClient") else None,
    op_filter=lambda ev: any(k == "hlo_op" for k, _ in ev.stats))

TINY = {"g500-s18": {"scale": 10}, "rand-s18": {"n_nodes": 1024,
                                                "n_edges": 16384}}


def tiny_root(tmp: Path, budget_words: int = 1 << 11) -> Path:
    """``BENCHMARK.json`` and ``bench/`` copied into ``tmp``, with every
    configuration cut to about a thousand vertices."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", tmp / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    for name, sizes in TINY.items():
        path = tmp / "bench" / "configs" / f"{name}.json"
        cfg = json.loads(path.read_text())
        cfg.update(sizes, mem_words=budget_words)
        path.write_text(json.dumps(cfg))
    return tmp


def run_tiny(root: Path, workload: str, seed: int = 3, seconds: float = 0.0,
             trace: bool = False, devices=None):
    import jax

    from bench.harness import Spec, run_cell

    kw = CPU_OPS if trace else {}
    return run_cell(Spec(root), workload, seed, seconds, trace,
                    devices or jax.devices(), time.perf_counter(), **kw)
