"""One pass: a full ``TriangleEngine.list()`` over the store the
program's own ingest built, ending in host rows. The first pass's rows
are checked against the reference in full; every later pass must equal
them row for row."""

import numpy as np

from bench import reference


def open(setup):
    return setup.triangle_engine()


def run(eng):
    return eng.list()


def check(results, dag):
    want = reference.reference_triangles(dag)
    first = results[0]
    faults = reference.row_faults(first, dag, want)
    differing = sum(not np.array_equal(r, first) for r in results[1:])
    checks = {k: (v, 0) for k, v in faults.items()}
    checks["passes_differing"] = (differing, 0)
    failed = len(results) if any(faults.values()) else differing
    return checks, failed
