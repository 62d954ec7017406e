"""The readers of the spans inside a box's lane, the listing's tail and
the sharded count path, and of the lanes' transfer counter, on synthetic
``Tracer`` snapshots: each reads its spans' seconds per pass inside the
pass's own ``engine.*`` span, and nothing where the program opened none
of them."""

from types import SimpleNamespace

import bench_helpers
import pytest

from bench import spans as S
from bench.harness import RunView, Spec

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


def one_box(t, kind):
    """One box at ``t``: the caller waits on the prefetcher, then pads,
    puts and runs it (and reads a listing's triangles off its hits)."""
    done = t + (2.0 if kind == "list" else 1.5)
    box = [("box.wait", CALLER, t, t + 0.25),
           ("box.compute", CALLER, t + 0.25, done),
           ("lane.pad", CALLER, t + 0.25, t + 0.75),
           ("lane.put", CALLER, t + 0.75, t + 1.0),
           ("lane.run", CALLER, t + 1.0, t + 1.5)]
    if kind == "list":
        box.append(("lane.readout", CALLER, t + 1.5, t + 2.0))
    return box + [("box.fetch", PRODUCER, t + 2.0, t + 3.0)]


def one_pass(t, kind):
    """A pass from ``t`` to ``t + 10``: three boxes, then a listing
    concatenates and sorts its rows."""
    recs = [(f"engine.{kind}", CALLER, t, t + 10.0),
            ("engine.plan", CALLER, t, t + 0.5)]
    for b in range(3):
        recs += one_box(t + 1.0 + 2.0 * b, kind)
    if kind == "list":
        recs += [("list.concat", CALLER, t + 7.0, t + 7.5),
                 ("engine.canonical", CALLER, t + 7.5, t + 9.5)]
    return recs


def sharded_pass(t):
    return [("engine.count", CALLER, t, t + 10.0),
            ("shard.stage", CALLER, t, t + 1.0),
            ("shard.edges", CALLER, t + 1.0, t + 4.0),
            ("shard.slices", CALLER, t + 4.0, t + 6.0),
            ("shard.put", CALLER, t + 6.0, t + 7.5),
            ("shard.run", CALLER, t + 7.5, t + 9.5)]


def view(records, passes, transfer_bytes=0):
    sp = S.spans_from_snapshot(snapshot(records))
    return RunView(passes=passes, spans=sp,
                   stats=SimpleNamespace(
                       device_transfer_bytes=transfer_bytes),
                   compiles=0, trace=None, graph_words=0, peaks={})


TWO_COUNTS = one_pass(0.0, "count") + one_pass(20.0, "count")
TWO_LISTS = one_pass(0.0, "list") + one_pass(20.0, "list")
TWO_SHARDED = sharded_pass(0.0) + sharded_pass(20.0)

# reader -> (records it reads, seconds per pass)
SPAN_READERS = {
    "lane_pad_s.count": (TWO_COUNTS, 1.5),
    "lane_put_s.count": (TWO_COUNTS, 0.75),
    "lane_run_s.count": (TWO_COUNTS, 1.5),
    "prefetch_wait_s.count": (TWO_COUNTS, 0.75),
    "lane_pad_s.list": (TWO_LISTS, 1.5),
    "lane_put_s.list": (TWO_LISTS, 0.75),
    "lane_run_s.list": (TWO_LISTS, 1.5),
    "lane_readout_s.list": (TWO_LISTS, 1.5),
    "list_concat_s": (TWO_LISTS, 0.5),
    "list_sort_s": (TWO_LISTS, 2.0),
    "shard_stage_s.count": (TWO_SHARDED, 1.0),
    "shard_edges_s.count": (TWO_SHARDED, 3.0),
    "shard_slices_s.count": (TWO_SHARDED, 2.0),
    "shard_put_s.count": (TWO_SHARDED, 1.5),
    "shard_run_s.count": (TWO_SHARDED, 2.0),
}

# a program that opens none of the new spans: only the pass, the boxes'
# stages and the prefetch thread
BARE = [r for r in TWO_COUNTS + TWO_LISTS
        if r[0] in ("engine.count", "engine.list", "box.compute",
                    "box.fetch")]


def reader(name):
    return Spec(bench_helpers.ROOT).metric_reader(name)


@pytest.mark.parametrize("name", sorted(SPAN_READERS))
def test_span_reader_reads_seconds_per_pass(name):
    records, want = SPAN_READERS[name]
    assert reader(name).read(view(records, passes=2)) \
        == pytest.approx(want)


@pytest.mark.parametrize("name", sorted(SPAN_READERS))
def test_span_reader_reads_nothing_without_its_spans(name):
    assert reader(name).read(view(BARE, passes=2)) is None


@pytest.mark.parametrize("name", sorted(SPAN_READERS))
def test_span_reader_counts_only_its_own_entry(name):
    """A ``.count`` reader finds nothing in listing passes, and the
    reverse: each metric moves its own cell's end-to-end time."""
    records, _ = SPAN_READERS[name]
    other = [(n.replace("engine.count", "engine.x")
              .replace("engine.list", "engine.x"), t, s, e)
             for n, t, s, e in records]
    assert reader(name).read(view(other, passes=2)) in (None, 0.0)


@pytest.mark.parametrize("shipped,want", [(1_250_000_000, 1250.0),
                                          (0, None)])
def test_lane_put_mb_reads_the_last_pass_transfer(shipped, want):
    got = reader("lane_put_mb.count").read(
        view(TWO_COUNTS, passes=2, transfer_bytes=shipped))
    assert got == (None if want is None else pytest.approx(want))


def test_lane_put_mb_without_the_counter_reads_nothing():
    run = view(TWO_COUNTS, passes=2)
    run.stats = SimpleNamespace()
    assert reader("lane_put_mb.count").read(run) is None


def test_lane_spans_cover_the_box():
    """On the synthetic count pass the lane spans sum to the box's
    compute: the sum the benchmark checks on the chip."""
    run = view(TWO_COUNTS, passes=2)
    lanes = sum(reader(f"lane_{p}_s.count").read(run)
                for p in ("pad", "put", "run"))
    assert lanes == pytest.approx(reader("box_compute_s.count").read(run))
