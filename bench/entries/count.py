"""One pass: a whole-graph ``TriangleEngine.count()`` over the store the
program's own ingest built, ending in a host int. Every pass's count
must equal the reference's."""

from bench import reference


def open(setup):
    return setup.triangle_engine()


def run(eng):
    return eng.count()


def check(results, dag):
    want = reference.reference_triangles(dag)
    gaps = [abs(int(c) - want) for c in results]
    return {"count_gap": (max(gaps), 0)}, sum(g != 0 for g in gaps)
