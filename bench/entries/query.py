"""One pass: a whole-graph triangle count through the general-purpose
``QueryEngine`` (``patterns.triangle()``) over the store the program's own
ingest built, ending in a host int. Every pass's count must equal the
reference's."""

from bench import reference


def open(setup):
    from repro.query import QueryEngine, patterns

    setup.triangle_engine()                 # writes the store
    return QueryEngine(patterns.triangle(), store=setup.store_path,
                       mem_words=setup.cfg["mem_words"],
                       tracer=setup.tracer, **setup.options)


def run(eng):
    return eng.count()


def check(results, dag):
    want = reference.reference_triangles(dag)
    gaps = [abs(int(c) - want) for c in results]
    return {"count_gap": (max(gaps), 0)}, sum(g != 0 for g in gaps)
