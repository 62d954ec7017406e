"""The harness finds configurations, generators, traffic mixes, entries
and per-layer metrics by name: a new one is new files and a
``BENCHMARK.json`` entry, with no file of the harness edited. And
``BENCHMARK.json`` keeps its shape."""

import hashlib
import json
import re

import bench_helpers
import pytest

from bench.harness import Spec

ROOT = bench_helpers.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def digest(root):
    return {p.relative_to(root): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in (root / "bench").rglob("*") if p.is_file()}


def test_new_config_traffic_and_metric_need_only_new_files(tmp_path):
    root = bench_helpers.tiny_root(tmp_path)
    before = digest(root)
    (root / "bench" / "configs" / "er-tiny.json").write_text(json.dumps({
        "generator": "rand", "n_nodes": 512, "n_edges": 4096,
        "structure_seed": 3,
        "store": {"orientation": "degree", "chunk_rows": 256,
                  "ingest_budget_words": 1 << 16},
        "mem_words": 1 << 10}))
    (root / "bench" / "traffic" / "count-hl.json").write_text(json.dumps({
        "entry": "count", "metric": "count_s", "warm_passes": 1,
        "engine": {"skew": "heavy_light"}}))
    (root / "bench" / "metrics" / "host_boxes.count.py").write_text(
        "LAYER = 'Lanes'\nUNIT = 'boxes'\nSOURCE = 'program_counter'\n"
        "MOVES = 'count_s'\n\n\ndef read(run):\n"
        "    return run.stats.n_host_boxes\n")
    doc = json.loads((root / "BENCHMARK.json").read_text())
    doc["configs"].append({"name": "er-tiny", "source": "test",
                           "file": "bench/configs/er-tiny.json",
                           "reduced": [], "why": "test"})
    doc["workloads"].append({"name": "er-tiny.count-hl", "config": "er-tiny",
                             "traffic": "count-hl", "chips": 1,
                             "why": "test"})
    doc["end_to_end"][0]["workloads"].append("er-tiny.count-hl")
    doc["per_layer"].append({"name": "host_boxes.count", "unit": "boxes",
                             "better": "higher", "source": "program_counter",
                             "layer": "Lanes", "moves": "count_s",
                             "workloads": ["er-tiny.count-hl"]})
    (root / "BENCHMARK.json").write_text(json.dumps(doc))

    plain = bench_helpers.run_tiny(root, "er-tiny.count-hl")
    assert plain.correct
    assert set(plain.metrics) == {"count_s", "setup_s"}
    traced = bench_helpers.run_tiny(root, "er-tiny.count-hl", trace=True)
    assert traced.correct
    assert traced.metrics["host_boxes.count"]["value"] > 0
    assert traced.metrics["host_boxes.count"]["unit"] == "boxes"
    after = digest(root)
    assert {k: v for k, v in after.items() if k in before} == before


CLIQUE_GRAPH = """
import numpy as np


def edges(cfg):
    i, j = np.triu_indices(cfg["n_nodes"], k=1)
    return (i.astype(np.int64), j.astype(np.int64)), cfg["n_nodes"]
"""

ROWS_ENTRY = """
from bench import reference


def open(setup):
    return setup.triangle_engine()


def run(eng):
    return len(eng.list())


def check(results, dag):
    want = reference.reference_triangles(dag)
    gaps = [abs(n - want) for n in results]
    return {"rows_gap": (max(gaps), 0)}, sum(g != 0 for g in gaps)
"""


def test_new_generator_and_entry_need_only_new_files(tmp_path):
    """A mix whose pass is a new entry point, over a graph of a new
    generator: the complete graph K48, C(48, 3) triangles."""
    root = bench_helpers.tiny_root(tmp_path)
    before = digest(root)
    (root / "bench" / "graphs" / "clique.py").write_text(CLIQUE_GRAPH)
    (root / "bench" / "entries" / "rows.py").write_text(ROWS_ENTRY)
    (root / "bench" / "configs" / "k48.json").write_text(json.dumps({
        "generator": "clique", "n_nodes": 48,
        "store": {"orientation": "degree", "chunk_rows": 16,
                  "ingest_budget_words": 1 << 12},
        "mem_words": 1 << 8}))
    (root / "bench" / "traffic" / "rows-loop.json").write_text(json.dumps({
        "entry": "rows", "metric": "rows_s", "warm_passes": 1}))
    doc = json.loads((root / "BENCHMARK.json").read_text())
    doc["configs"].append({"name": "k48", "source": "test",
                           "file": "bench/configs/k48.json",
                           "reduced": [], "why": "test"})
    doc["workloads"].append({"name": "k48.rows", "config": "k48",
                             "traffic": "rows-loop", "chips": 1,
                             "why": "test"})
    doc["end_to_end"].append({"name": "rows_s", "unit": "s",
                              "better": "lower", "bound": 0.05,
                              "source": "host_clock",
                              "workloads": ["k48.rows"]})
    (root / "BENCHMARK.json").write_text(json.dumps(doc))

    res = bench_helpers.run_tiny(root, "k48.rows")
    assert res.correct and res.attempted >= 1
    assert res.checks == {"rows_gap": {"value": 0, "limit": 0}}
    assert set(res.metrics) == {"rows_s", "setup_s"}
    # the entry is read anew each run: a pass that drops a row is refused
    (root / "bench" / "entries" / "rows.py").write_text(
        ROWS_ENTRY.replace("len(eng.list())", "len(eng.list()) - 1"))
    res = bench_helpers.run_tiny(root, "k48.rows")
    assert not res.correct
    assert res.checks["rows_gap"]["value"] == 1
    after = digest(root)
    assert {k: v for k, v in after.items() if k in before} == before


def test_every_entry_has_its_file_and_the_file_agrees():
    spec = Spec(ROOT)
    doc = spec.doc
    for c in doc["configs"]:
        cfg = spec.config(c["name"])
        assert cfg["name"] == c["name"]
        assert cfg["reduced"] == c["reduced"]
        assert cfg["source"] == c["source"]
    for w in doc["workloads"]:
        spec.generator(spec.config(w["config"])["generator"]).edges
        traffic = spec.traffic(w["traffic"])
        assert traffic["metric"] in {m["name"] for m in doc["end_to_end"]}
        entry = spec.entry(traffic["entry"])
        assert callable(entry.open) and callable(entry.run) \
            and callable(entry.check)
    for m in doc["per_layer"]:
        mod = spec.metric_reader(m["name"])
        assert (mod.LAYER, mod.UNIT, mod.SOURCE, mod.MOVES) == \
            (m["layer"], m["unit"], m["source"], m["moves"])
        for w in m["workloads"]:
            e2e = {x["name"] for x in spec.end_to_end(w)}
            assert m["moves"] in e2e, (m["name"], w)


def test_missing_files_are_named():
    spec = Spec(ROOT)
    with pytest.raises(ValueError, match="no traffic file"):
        spec.traffic("no-such-mix")
    with pytest.raises(ValueError, match="no metric reader"):
        spec.metric_reader("no_such_metric")
    with pytest.raises(ValueError, match="no entry"):
        spec.entry("no_such_entry")
    with pytest.raises(ValueError, match="no generator"):
        spec.generator("no_such_generator")
    with pytest.raises(ValueError, match="no workloads entry"):
        spec.workload("no-such-cell")


def test_benchmark_json_shape():
    doc = json.loads((ROOT / "BENCHMARK.json").read_text())
    assert set(doc) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert doc["paths"] == ["bench"]
    assert doc["command"] == ["python3", "bench/run.py"]
    assert 1 <= doc["run_seconds"] <= 51
    names = set()
    for key, keys in (("configs", {"name", "source", "file", "reduced",
                                   "why"}),
                      ("workloads", {"name", "config", "traffic", "chips",
                                     "why"})):
        for e in doc[key]:
            assert set(e) == keys
            assert NAME.match(e["name"]) and e["name"] not in names
            names.add(e["name"])
            assert 1 <= len(e["why"]) <= 200
    cells = {w["name"] for w in doc["workloads"]}
    four = sum(w["chips"] == 4 for w in doc["workloads"])
    assert four <= max(1, len(cells) // 2)
    seen = set()
    for m in doc["end_to_end"] + doc["per_layer"]:
        assert NAME.match(m["name"]) and m["name"] not in seen
        seen.add(m["name"])
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= cells
    for m in doc["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    assert "setup_s" in {m["name"] for m in doc["end_to_end"]}
    layers = {}
    for m in doc["per_layer"]:
        assert m["moves"] in {x["name"] for x in doc["end_to_end"]}
        layers.setdefault(m["name"].split(".")[0], set()).add(m["layer"])
    assert all(len(v) == 1 for v in layers.values())
    for w in cells:
        assert any(w in m["workloads"] for m in doc["per_layer"])
