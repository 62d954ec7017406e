"""The comparison that decides ``correct`` refuses the control and every
fault the cells can have, planted in the program under a run that skips
the harness's look for a chip (tiny sizes, on the CPU).

Faults: half of the boxes left out and the count doubled (half the
batch, the mean over the rest); an answer altered where a box's lane
produces it; the exchange between chips left out (a four-chip count
cell of the test's own on four virtual CPU devices, in a child
process). A pass keeps no state from one pass to the next, so the fault
of a state left unchanged does not apply."""

import contextlib
import os
import subprocess
import sys
import textwrap

import bench_helpers
import numpy as np
import pytest

from bench.control import approximate_engine

ROOT = bench_helpers.ROOT


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return bench_helpers.tiny_root(tmp_path_factory.mktemp("bench"))


@contextlib.contextmanager
def patched(obj, name, make):
    orig = getattr(obj, name)
    setattr(obj, name, make(orig))
    try:
        yield
    finally:
        setattr(obj, name, orig)


def half_boxes(orig):
    def run(self, boxes):
        boxes = list(boxes)
        out = orig(self, boxes[::2])
        return out * 2 if np.isscalar(out) else out
    return run


def count_plus_one(orig):
    def count(self, slc):
        return orig(self, slc) + (1 if slc.box == self._first else 0)
    return count


def first_row_altered(orig):
    def lst(self, slc):
        rows = orig(self, slc)
        if rows is not None and len(rows):
            rows = rows.copy()
            rows[0, 2] = rows[0, 1]
        return rows
    return lst


def executor_with_first_box(orig):
    def run(self, boxes):
        boxes = list(boxes)
        self._first = boxes[0] if boxes else None
        return orig(self, boxes)
    return run


@pytest.mark.parametrize("cell", ["g500-s18.count", "rand-s18.count",
                                  "g500-s18.list"])
def test_sound_run_is_correct(root, cell):
    res = bench_helpers.run_tiny(root, cell, seed=2 ** 31 + 3)
    assert res.correct and res.failed == 0
    assert all(c["value"] == 0 == c["limit"] for c in res.checks.values())


@pytest.mark.parametrize("cell", ["g500-s18.count", "rand-s18.count",
                                  "g500-s18.list"])
def test_control_is_not_correct(root, cell):
    with approximate_engine():
        res = bench_helpers.run_tiny(root, cell, seed=2 ** 31 + 3)
    assert not res.correct
    key = "row_count_gap" if cell.endswith("list") else "count_gap"
    assert res.checks[key]["value"] > res.checks[key]["limit"]


@pytest.mark.parametrize("cell,method", [("g500-s18.count", "run_count"),
                                         ("g500-s18.list", "run_list")])
def test_half_the_boxes_is_not_correct(root, cell, method):
    from repro.core.executor import StreamingExecutor

    with patched(StreamingExecutor, method, half_boxes):
        res = bench_helpers.run_tiny(root, cell)
    assert not res.correct and res.failed == res.attempted


def test_count_altered_at_its_lane_is_not_correct(root):
    from repro.core.executor import StreamingExecutor as SE

    with patched(SE, "run_count", executor_with_first_box), \
            patched(SE, "_count_slice", count_plus_one):
        res = bench_helpers.run_tiny(root, "rand-s18.count")
    assert not res.correct
    assert res.checks["count_gap"]["value"] == 1


def test_row_altered_at_its_lane_is_not_correct(root):
    from repro.core.executor import StreamingExecutor as SE

    with patched(SE, "_list_slice", first_row_altered):
        res = bench_helpers.run_tiny(root, "g500-s18.list")
    assert not res.correct
    assert res.checks["pairs_not_edges"]["value"] > 0 \
        or res.checks["rows_out_of_order"]["value"] > 0


FOUR_CHIPS = textwrap.dedent("""
    import json, sys
    sys.path[:0] = [{tests!r}]
    import bench_helpers
    from pathlib import Path
    import repro.core.engine as E

    root = Path({root!r})
    out = {{}}
    res = bench_helpers.run_tiny(root, "g500-s18.count.x4")
    out["sound"] = [res.correct, res.checks, res.device["count"]]
    orig = E.sharded_count_program

    def no_exchange(mesh, chunk):
        run = orig(mesh, chunk)
        # each chip's partial stays where it is: only chip 0's is summed
        return lambda *a: run(*a).at[1:].set(0)

    E.sharded_count_program = no_exchange
    res = bench_helpers.run_tiny(root, "g500-s18.count.x4")
    out["fault"] = [res.correct, res.checks]
    print(json.dumps(out))
""")


def test_exchange_between_chips_left_out_is_not_correct(tmp_path):
    root = bench_helpers.tiny_root(tmp_path)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = FOUR_CHIPS.format(tests=str(ROOT / "bench" / "tests"),
                             root=str(root))
    p = subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-3000:]
    import json

    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["sound"][0] is True and out["sound"][2] == 4
    assert out["fault"][0] is False
    assert out["fault"][1]["count_gap"]["value"] > 0
