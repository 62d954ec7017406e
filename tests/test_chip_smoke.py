"""``chip_smoke.py``'s phases on the CPU at a tiny size, kernels in
interpret mode, against the same scipy references the chip run uses; plus
the guarantee that the script refuses to run without a TPU."""

import os
import sys

import jax
import numpy as np
import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..")
sys.path.insert(0, ROOT)

import chip_smoke as cs  # noqa: E402
from repro.core.leapfrog import lftj_query_count  # noqa: E402
from repro.core.lftj_jax import orient_edges  # noqa: E402
from repro.core.triearray import TrieArray  # noqa: E402
from repro.query.patterns import four_clique  # noqa: E402

SCALE, SMALL_SCALE, SEED = 9, 7, 3


@pytest.fixture(scope="module")
def graph(tmp_path_factory):
    d = tmp_path_factory.mktemp("smoke")
    eng, src, dst = cs.build_store(d / "g.store", SCALE, SEED, kernels=False)
    dag = cs.reference_dag(src, dst)
    mem_words, n_boxes = cs.pick_mem_words(d / "g.store")
    return dict(store=d / "g.store", src=src, dst=dst, dag=dag,
                want=cs.reference_triangles(dag), mem_words=mem_words,
                n_boxes=n_boxes, nv=eng.nv)


@pytest.fixture(scope="module")
def k4_graph(tmp_path_factory):
    d = tmp_path_factory.mktemp("smoke_k4")
    _, src, dst = cs.build_store(d / "k4.store", SMALL_SCALE, SEED,
                                 kernels=False)
    dag = cs.reference_dag(src, dst)
    mem_words, _ = cs.pick_mem_words(d / "k4.store")
    return dict(store=d / "k4.store", src=src, dst=dst, dag=dag,
                tri=cs.reference_triangles(dag),
                k4=cs.reference_four_cliques(dag), mem_words=mem_words)


def test_references_agree_with_scalar_lftj(k4_graph):
    a, b = orient_edges(k4_graph["src"], k4_graph["dst"], "degree")
    ta = TrieArray.from_edges(a, b)
    q = four_clique()
    order = [f"v{i}" for i in range(4)]
    assert k4_graph["k4"] == lftj_query_count(q.atoms, order, {"E": ta})
    tri_atoms = q.atoms[:1] + [q.atoms[1], q.atoms[3]]   # v0v1 v0v2 v1v2
    assert k4_graph["tri"] == lftj_query_count(tri_atoms, order[:3],
                                               {"E": ta})
    assert k4_graph["k4"] > 0


def test_store_is_graph500_shaped(graph):
    assert graph["nv"] <= 1 << SCALE
    assert len(graph["src"]) == 16 << SCALE
    assert graph["n_boxes"] >= cs.MIN_BOXES
    assert graph["want"] > 0


@pytest.mark.parametrize("backend,skew,own_lane", [
    ("auto", "uniform", None), ("auto", "heavy_light", None),
    ("pallas", "uniform", "pallas")])
def test_phase_count(graph, backend, skew, own_lane):
    res = cs.phase_count(graph["store"], graph["want"], graph["mem_words"],
                         kernels=False, backend=backend, skew=skew,
                         own_lane=own_lane, warm=False)
    assert res["count"] == graph["want"]
    assert res["boxes"] >= cs.MIN_BOXES


@pytest.mark.parametrize("lane", ["dense", "fused"])
def test_phase_count_small_store(k4_graph, lane):
    res = cs.phase_count(k4_graph["store"], k4_graph["tri"],
                         k4_graph["mem_words"], kernels=False, backend=lane,
                         own_lane=lane, warm=False)
    assert res["lanes"][f"n_{lane}_boxes"] > 0


def test_phase_count_catches_a_wrong_reference(graph):
    with pytest.raises(cs.SmokeFailure, match="reference"):
        cs.phase_count(graph["store"], graph["want"] + 1,
                       graph["mem_words"], kernels=False, warm=False)


def test_phase_count_refuses_uncompiled_kernels_on_chip_settings(graph):
    """kernels=True demands the engine resolved to compiled kernels,
    which it never does on the CPU."""
    with pytest.raises(cs.SmokeFailure, match="use_pallas_kernels"):
        cs.phase_count(graph["store"], graph["want"], graph["mem_words"],
                       kernels=True, warm=False)


def test_phase_list(graph):
    res = cs.phase_list(graph["store"], graph["dag"], graph["want"],
                        graph["mem_words"], kernels=False)
    assert res["rows"] == graph["want"]


@pytest.mark.parametrize("canonical", [False, True])
def test_check_triangle_rows_rejects_duplicates(graph, canonical):
    from repro.core.engine import TriangleEngine

    rows = TriangleEngine(store=graph["store"],
                          use_pallas_kernels=False).list()
    cs.check_triangle_rows(rows, graph["dag"], graph["want"], "t",
                           canonical=canonical, step=100)
    rows = np.concatenate([rows[:-1], rows[:1]])
    with pytest.raises(cs.SmokeFailure, match="duplicate"):
        cs.check_triangle_rows(rows, graph["dag"], graph["want"], "t",
                               canonical=canonical, step=100)


def test_check_triangle_rows_rejects_a_non_edge(graph):
    from repro.core.engine import TriangleEngine

    rows = TriangleEngine(store=graph["store"],
                          use_pallas_kernels=False).list()
    rows[5, 2] = graph["dag"].shape[0] - 1 - rows[5, 2]
    with pytest.raises(cs.SmokeFailure):
        cs.check_triangle_rows(rows, graph["dag"], graph["want"], "t")


def test_phase_query_triangle(graph):
    res = cs.phase_query(graph["store"], "triangle", graph["want"],
                         graph["mem_words"], kernels=False, warm=False)
    assert res["count"] == graph["want"]


def test_phase_query_four_clique(k4_graph):
    res = cs.phase_query(k4_graph["store"], "four_clique", k4_graph["k4"],
                         k4_graph["mem_words"], kernels=False, warm=False)
    assert res["count"] == k4_graph["k4"]


def test_phase_server(k4_graph):
    res = cs.phase_server(k4_graph["store"], k4_graph["dag"],
                          k4_graph["tri"], k4_graph["k4"],
                          max(4 * k4_graph["mem_words"], 1 << 12),
                          kernels=False)
    assert res["rows"] == k4_graph["tri"]


def test_phase_sharded_and_fabric(graph):
    devices = jax.devices()
    res = cs.phase_sharded(graph["store"], graph["want"],
                           graph["mem_words"], devices, kernels=False)
    assert res["count"] == res["one_chip"] == graph["want"]
    res = cs.phase_fabric(graph["src"], graph["dst"], graph["want"],
                          graph["mem_words"], len(devices), kernels=False)
    assert res["count"] == graph["want"]


def test_main_refuses_a_cpu_only_run(tmp_path, capsys):
    with pytest.raises(SystemExit) as exc:
        cs.main(["--workdir", str(tmp_path / "w")])
    assert exc.value.code not in (0, None)
    assert capsys.readouterr().out == ""
    assert not (tmp_path / "w").exists()


def test_run_dir_removes_only_what_the_run_made(tmp_path):
    (tmp_path / "keep.txt").write_text("user data")
    with cs.run_dir(tmp_path) as d:
        assert d.parent == tmp_path and not any(d.iterdir())
        (d / "g.store").mkdir()
    assert not d.exists()
    assert (tmp_path / "keep.txt").read_text() == "user data"
    assert sorted(p.name for p in tmp_path.iterdir()) == ["keep.txt"]
