"""``TriangleEngine._canonical``: the listing's canonical order.

Every row comes out as ``(min, mid, max)``, the rows in lexicographic
order, as a C-contiguous int64 array, equal row for row to a plain
reference (a row sort, then ``np.lexsort``). Ids of up to 21 bits take
the packed-key sort; a graph whose ids need 22 bits takes the
``np.lexsort`` fallback, counted in ``EngineStats.n_canonical_fallbacks``.
"""

import numpy as np
import pytest

from repro.core import TriangleEngine
from repro.obs import Tracer

WIDE = 1 << 21          # the first id that no longer packs three to an int64


def reference_order(rows: np.ndarray) -> np.ndarray:
    rows = np.sort(np.asarray(rows, dtype=np.int64).reshape(-1, 3), axis=1)
    return rows[np.lexsort((rows[:, 2], rows[:, 1], rows[:, 0]))]


def shuffled_triangles(n_rows: int, top: int, seed: int) -> np.ndarray:
    """Distinct-id rows below ``top`` (the largest ids included), each
    row's columns in a random order."""
    rng = np.random.default_rng(seed)
    rows = np.sort(rng.integers(0, top, size=(n_rows, 3)), axis=1)
    rows = rows[(rows[:, 0] < rows[:, 1]) & (rows[:, 1] < rows[:, 2])]
    rows = np.concatenate([rows, [[top - 3, top - 2, top - 1],
                                  [0, 1, top - 1], [0, 1, 2]]])
    rows = np.concatenate([rows, rows[:50]])        # rows listed twice
    return rng.permuted(rows, axis=1)


INPUTS = {
    "shuffled": lambda top: shuffled_triangles(100_000, top, seed=top),
    "empty": lambda top: np.zeros((0, 3), np.int64),
    "one_row": lambda top: np.array([[top - 1, 7, top - 2]], np.int32),
    # at 21 bits three largest ids pack to 2**63 - 1, which must still
    # sort last and not wrap negative
    "extremes": lambda top: np.array([[top - 1] * 3, [0, 0, 0],
                                      [top - 1, 0, top - 2]]),
}


@pytest.mark.parametrize("nv", [WIDE, WIDE + 1], ids=["21bit", "22bit"])
@pytest.mark.parametrize("case", sorted(INPUTS))
def test_canonical_matches_row_sort_then_lexsort(case, nv):
    eng = TriangleEngine(np.array([0]), np.array([nv - 1]), tracer=Tracer())
    assert eng.nv == nv
    rows = INPUTS[case](nv)
    got = eng._canonical(rows)
    assert got.dtype == np.int64
    assert got.flags.c_contiguous
    assert got.shape == (len(rows), 3)
    np.testing.assert_array_equal(got, reference_order(rows))
    packed = nv <= WIDE
    assert eng.stats.n_canonical_fallbacks == int(not packed and len(rows) > 0)
    [span] = [e for e in eng.tracer.snapshot()
              if e["ph"] == "B" and e["name"] == "engine.canonical"]
    assert span["args"] == {"rows": len(rows), "packed": packed}
