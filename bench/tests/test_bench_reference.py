"""The copied generators and references against the program: at scales
10 to 12 the engine's count and listing over each configuration's graph
equal the plain reference, and the row check finds each kind of fault."""

import bench_helpers
import numpy as np
import pytest

from bench import reference as R
from bench.graphs.rand import random_edges
from bench.harness import Spec, SpecError

make_graph = Spec(bench_helpers.ROOT).make_graph

G500 = dict(generator="graph500", edge_factor=16,
            initiator=[0.57, 0.19, 0.19], structure_seed=0)


def configs():
    for scale in (10, 11, 12):
        yield f"g500-s{scale}", dict(G500, scale=scale)
        yield f"rand-s{scale}", dict(generator="rand", n_nodes=1 << scale,
                                     n_edges=16 << scale, structure_seed=0)


@pytest.mark.parametrize("name,cfg", list(configs()))
def test_engine_count_and_list_equal_reference(name, cfg):
    from repro.core.engine import TriangleEngine

    src, dst = make_graph(cfg, seed=2 ** 31 + 7)
    dag = R.reference_dag(src, dst)
    want = R.reference_triangles(dag)
    eng = TriangleEngine(src, dst, orientation="degree", mem_words=1 << 12,
                         use_pallas_kernels=False)
    assert eng.count() == want
    faults = R.row_faults(eng.list(), dag, want)
    assert faults == {"row_count_gap": 0, "rows_out_of_order": 0,
                      "pairs_not_edges": 0}


def test_graph500_copy_matches_program_generator():
    from repro.data.graphs import graph500_edges

    s_prog, d_prog = graph500_edges(11, seed=4)
    s_copy, d_copy = make_graph(dict(G500, scale=11, structure_seed=4),
                                  seed=9)
    deg = lambda s, d: np.sort(np.bincount(np.concatenate([s, d]),  # noqa
                                           minlength=1 << 11))
    # the same Kronecker edges under another labelling
    assert np.array_equal(deg(s_prog, d_prog), deg(s_copy, d_copy))
    assert R.reference_triangles(R.reference_dag(s_prog, d_prog)) \
        == R.reference_triangles(R.reference_dag(s_copy, d_copy))


def test_rand_copy_matches_program_generator():
    from repro.data.graphs import random_graph

    s_prog, d_prog = random_graph(1 << 10, 1 << 14, seed=5)
    s_copy, d_copy = random_edges(1 << 10, 1 << 14, structure_seed=5)
    assert np.array_equal(s_prog, s_copy) and np.array_equal(d_prog, d_copy)


def test_seed_relabels_the_same_graph():
    cfg = dict(G500, scale=10)
    a = make_graph(cfg, seed=1)
    b = make_graph(cfg, seed=2 ** 32 + 1)
    assert not np.array_equal(a[0], b[0])
    assert R.reference_triangles(R.reference_dag(*a)) \
        == R.reference_triangles(R.reference_dag(*b))
    again = make_graph(cfg, seed=1)
    assert np.array_equal(a[0], again[0]) and np.array_equal(a[1], again[1])


def test_unknown_generator_is_refused():
    with pytest.raises(SpecError, match="no generator"):
        make_graph({"generator": "nope"}, seed=0)


@pytest.fixture(scope="module")
def k5_rows():
    """K5 plus a pendant edge: 10 triangles, canonical rows."""
    i, j = np.triu_indices(5, k=1)
    src = np.concatenate([i, [4]])
    dst = np.concatenate([j, [5]])
    dag = R.reference_dag(src, dst)
    rows = np.array([r for r in __import__("itertools").combinations(
        range(5), 3)], dtype=np.int64)
    return dag, rows


def test_row_faults_pass_the_right_listing(k5_rows):
    dag, rows = k5_rows
    assert R.reference_triangles(dag) == 10
    assert set(R.row_faults(rows, dag, 10).values()) == {0}
    assert set(R.row_faults(rows, dag, 10, step=3).values()) == {0}


def test_row_faults_find_each_fault(k5_rows):
    dag, rows = k5_rows
    missing = R.row_faults(rows[1:], dag, 10)
    assert missing["row_count_gap"] == 1
    dup = np.concatenate([rows[:1], rows])
    assert R.row_faults(dup, dag, 10)["rows_out_of_order"] == 1
    swapped = rows[[1, 0] + list(range(2, 10))]
    assert R.row_faults(swapped, dag, 10, step=4)["rows_out_of_order"] == 1
    unsorted_row = rows.copy()
    unsorted_row[3] = unsorted_row[3][::-1]
    assert R.row_faults(unsorted_row, dag, 10)["rows_out_of_order"] >= 1
    wrong = rows.copy()
    wrong[-1, 2] = 5                     # (2, 3, 5): 2-5, 3-5 no edges
    assert R.row_faults(wrong, dag, 10)["pairs_not_edges"] == 2
    assert R.row_faults(rows[:, :2], dag, 10)["pairs_not_edges"] == 30


@pytest.mark.parametrize("name", ["g500-s18", "rand-s18"])
def test_config_budget_is_largest_power_of_two_with_16_boxes(name, tmp_path):
    """Each configuration's ``mem_words`` is what its assumed rule gives
    at full size, for the labelling of one seed."""
    import json

    from repro.core.engine import TriangleEngine

    cfg = json.loads((bench_helpers.ROOT / "bench" / "configs"
                      / f"{name}.json").read_text())
    store = cfg["store"]
    eng = TriangleEngine.ingest(
        tmp_path / "g.store", make_graph(cfg, seed=2 ** 31 + 1),
        orientation=store["orientation"], chunk_rows=store["chunk_rows"],
        ingest_budget_words=store["ingest_budget_words"],
        use_pallas_kernels=False)
    words = cfg["mem_words"]
    eng.mem_words = words
    assert len(eng.plan()) >= 16
    eng.mem_words = 2 * words
    assert len(eng.plan()) < 16
