"""The program's spans on the profiler's clock: under a ``jax.profiler``
capture on the CPU, each span of a traced count is also a host event of
the trace, where the harness's ``bench.window`` offset puts it."""

import bench_helpers
import numpy as np
import pytest

from bench import spans as S, trace_reduce as TR

WINDOW_US = 10            # the two bench.window events, apart at most
SPAN_MS = 1.0             # a span against its host event, apart at most


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """A traced store-backed count inside the harness's window, under the
    profiler as the harness runs it."""
    import jax

    from repro.core.engine import TriangleEngine
    from repro.data.graphs import rmat_graph
    from repro.obs import Tracer

    src, dst = rmat_graph(512, 6000, seed=21)
    tracer = Tracer()
    eng = TriangleEngine.ingest(
        tmp_path_factory.mktemp("store") / "g.store", (src, dst),
        orientation="degree", chunk_rows=256, mem_words=4096,
        backend="binary", tracer=tracer)
    want = eng.count()                     # compiles outside the capture
    log_dir = str(tmp_path_factory.mktemp("profile"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(TR.WINDOW), \
                tracer.span(TR.WINDOW):
            got = eng.count()
    finally:
        jax.profiler.stop_trace()
    assert got == want
    profile = jax.profiler.ProfileData.from_file(TR.find_xplane(log_dir))
    spans = S.spans_from_snapshot(tracer.snapshot())
    return profile, spans


def host_events(profile, name):
    return [(ev.start_ns * 1e-9, ev.end_ns * 1e-9)
            for plane in profile.planes if plane.name.startswith("/host")
            for line in plane.lines for ev in line.events
            if ev.name == name]


def window_pair(log_dir):
    """A harness-style window under its own capture: (the window the
    reducer finds, both ``bench.window`` host events)."""
    import time

    import jax

    from repro.obs import Tracer

    tracer = Tracer()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(TR.WINDOW), \
                tracer.span(TR.WINDOW):
            time.sleep(0.01)
    finally:
        jax.profiler.stop_trace()
    profile = jax.profiler.ProfileData.from_file(TR.find_xplane(log_dir))
    lo, hi = TR.window_of(profile)
    return (lo * 1e-9, hi * 1e-9), host_events(profile, TR.WINDOW)


def test_the_window_found_moves_by_under_ten_microseconds(tmp_path):
    """The trace now holds the harness's own ``bench.window`` annotation
    and the program span's mirror of it, nested: whichever the reducer
    finds, the window (and the offset the harness computes) is the
    same to within 10 us. The median of five captures, so that one
    preempted thread does not decide it."""
    moves = []
    for i in range(5):
        (lo, hi), both = window_pair(str(tmp_path / str(i)))
        assert len(both) == 2 and (lo, hi) in both
        moves.append(max(max(abs(s - lo), abs(e - hi)) for s, e in both))
    assert np.median(moves) < WINDOW_US * 1e-6, moves


@pytest.mark.parametrize("name", ["box.compute", "lane.run", "box.wait"])
def test_each_span_is_a_host_event_where_the_offset_puts_it(recorded,
                                                            name):
    profile, spans = recorded
    win = S.named(spans, TR.WINDOW)[0]
    lo, _ = TR.window_of(profile)
    shift = lo * 1e-9 - win.start          # as the harness computes it
    ours = [s for s in S.named(spans, name)
            if s.start >= win.start and s.end <= win.end]
    assert len(ours) > 1
    events = np.array(host_events(profile, name))
    assert len(events) == len(ours)
    for s in ours:
        gap = np.abs(events - [s.start + shift, s.end + shift]).max(axis=1)
        assert gap.min() < SPAN_MS * 1e-3, (name, s, gap.min())


def test_device_ops_fall_inside_the_lane_run_events(recorded):
    """The binary lane's program runs while its ``lane.run`` event is
    open: on one clock, the device work sits inside the host span."""
    profile, _ = recorded
    runs = host_events(profile, "lane.run")
    sel = bench_helpers.CPU_OPS["select"]
    keep = bench_helpers.CPU_OPS["op_filter"]
    ops = [(ev.start_ns * 1e-9, ev.end_ns * 1e-9)
           for plane in profile.planes for line in plane.lines
           if sel(plane.name, line.name) is not None
           for ev in line.events if keep(ev)
           and "_count_chunked" in str(TR._stat(ev, "hlo_module"))]
    assert ops
    inside = sum(any(s >= a and e <= b for a, b in runs) for s, e in ops)
    assert inside == len(ops)
