"""``bench/run.py`` refuses to run without a TPU, and in a checkout that
holds only the benchmark: non-zero exit and no result line."""

import os
import shutil
import subprocess
import sys

import bench_helpers

ROOT = bench_helpers.ROOT
ARGS = ["--workload", "g500-s18.count", "--seed", str(2 ** 31 + 11),
        "--seconds", "1", "--trace", "0"]


def run(cwd, script):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, str(script)] + ARGS, cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=120)


def no_result(out: str) -> bool:
    return not any(line.lstrip().startswith("{")
                   for line in out.splitlines())


def test_no_tpu_exits_nonzero_without_result():
    p = run(ROOT, ROOT / "bench" / "run.py")
    assert p.returncode != 0
    assert no_result(p.stdout) and p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_benchmark_alone_exits_nonzero_without_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = run(tmp_path, tmp_path / "bench" / "run.py")
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "not in this checkout" in p.stderr


def test_unknown_workload_exits_nonzero_without_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, str(ROOT / "bench" / "run.py"),
                        "--workload", "no-such-cell", "--seed", "1",
                        "--seconds", "1"], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode != 0 and p.stdout.strip() == ""


def test_cpu_result_names_its_device(tmp_path):
    res = bench_helpers.run_tiny(bench_helpers.tiny_root(tmp_path),
                                 "rand-s18.count")
    line = res.line()
    assert line.index('"checks"') > line.index('"device"')
    dev = res.device
    assert dev["platform"] == "cpu" and dev["count"] == 1
    assert "kind" in dev and "memory_peak_bytes" in dev
    assert set(res.metrics) == {"count_s", "setup_s"}
    assert res.metrics["count_s"]["unit"] == "s"
