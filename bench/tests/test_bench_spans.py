"""Span self-times on synthetic ``Tracer`` snapshots: a calling thread
in ``engine.count``/``engine.list`` and a prefetch thread in
``box.fetch``."""

import threading
import time

import bench_helpers  # noqa: F401
import pytest

from bench import spans as S

CALLER, PRODUCER = 1, 2


def snapshot(records):
    """(name, tid, start_s, end_s) -> Tracer.snapshot()-style events."""
    ev = []
    for sid, (name, tid, s, e) in enumerate(records, 1):
        ev.append({"ph": "B", "name": name, "ts": s * 1e6, "tid": tid,
                   "sid": sid, "parent": None, "lane": None})
        ev.append({"ph": "E", "ts": e * 1e6, "tid": tid, "sid": sid,
                   "lane": None})
    ev.sort(key=lambda r: r["ts"])
    return ev


# one count pass from 0 to 10 s: the prefetch thread fetches three boxes,
# the caller computes each once it arrives and then sums up
PASS = [("engine.count", CALLER, 0.0, 10.0),
        ("box.fetch", PRODUCER, 0.5, 2.0),
        ("box.fetch", PRODUCER, 2.0, 3.5),
        ("box.fetch", PRODUCER, 3.5, 7.5),
        ("box.compute", CALLER, 2.0, 4.0),
        ("box.compute", CALLER, 4.0, 5.0),
        ("box.compute", CALLER, 7.5, 9.0)]


def test_spans_from_snapshot_pairs_begin_and_end():
    sp = S.spans_from_snapshot(snapshot(PASS))
    assert len(sp) == len(PASS)
    assert sp[0].name == "engine.count" and sp[0].seconds == 10.0


def test_open_spans_are_left_out():
    ev = snapshot(PASS)
    ev.append({"ph": "B", "name": "open", "ts": 1.0, "tid": 3, "sid": 99,
               "parent": None, "lane": None})
    assert "open" not in {s.name for s in S.spans_from_snapshot(ev)}


def test_fetch_time_on_the_producer_thread_counts_inside_the_pass():
    sp = S.spans_from_snapshot(snapshot(PASS))
    assert S.seconds_in(sp, "box.fetch", "engine.count") == pytest.approx(7.0)
    assert S.seconds_in(sp, "box.compute", "engine.count") \
        == pytest.approx(4.5)


def test_wait_is_caller_time_outside_compute_before_the_last_box():
    sp = S.spans_from_snapshot(snapshot(PASS))
    # 0..2 planning and waiting, 5..7.5 waiting for the third box; the
    # tail 9..10 after the last box is not wait
    assert S.wait_before_last(sp, "box.compute", "engine.count") \
        == pytest.approx(4.5)


def test_wait_ignores_compute_spans_of_other_threads():
    other = PASS + [("box.compute", 3, 0.0, 9.5)]
    sp = S.spans_from_snapshot(snapshot(other))
    assert S.wait_before_last(sp, "box.compute", "engine.count") \
        == pytest.approx(4.5)


def test_tail_after_last_box():
    recs = [(n.replace("count", "list"), t, s, e) for n, t, s, e in PASS]
    sp = S.spans_from_snapshot(snapshot(recs))
    assert S.tail_after_last(sp, "box.compute", "engine.list") \
        == pytest.approx(1.0)
    assert S.tail_after_last(sp, "box.compute", "engine.count") is None


def test_two_passes_sum():
    second = [(n, t, s + 20.0, e + 20.0) for n, t, s, e in PASS]
    sp = S.spans_from_snapshot(snapshot(PASS + second))
    assert S.seconds_in(sp, "box.fetch", "engine.count") \
        == pytest.approx(14.0)
    assert S.wait_before_last(sp, "box.compute", "engine.count") \
        == pytest.approx(9.0)


def test_union_and_open_at():
    assert S.union_seconds([(0, 2), (1, 3), (5, 6)]) == pytest.approx(4.0)
    sp = S.spans_from_snapshot(snapshot(PASS))
    names = sorted(s.name for s in S.open_at(sp, 3.0))
    assert names == ["box.compute", "box.fetch"]
    assert [s.name for s in S.open_at(sp, 9.5)] == ["engine.count"]


def test_real_tracer_with_producer_thread():
    from repro.obs import Tracer

    tr = Tracer()
    with tr.span("engine.count"):
        t = threading.Thread(target=lambda: [
            tr.span("box.fetch").__exit__(None, None, None)
            for _ in range(3)])
        t.start()
        t.join(timeout=10)
        with tr.span("box.compute"):
            time.sleep(0.01)
    assert not t.is_alive()
    sp = S.spans_from_snapshot(tr.snapshot())
    assert len(S.named(sp, "box.fetch")) == 3
    fetch_tids = {s.tid for s in S.named(sp, "box.fetch")}
    assert fetch_tids != {S.named(sp, "engine.count")[0].tid}
    assert S.seconds_in(sp, "box.compute", "engine.count") >= 0.01
