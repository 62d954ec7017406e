"""Host spans of the program's ``obs.trace.Tracer``, reduced to the
per-layer seconds the metric readers report.

Spans are read from ``Tracer.snapshot()`` (begin/end events with
microsecond stamps and thread ids) and turned into closed intervals.
Spans of different threads are related by time, not by parent id: the
box pipeline's prefetch thread opens ``box.fetch`` outside the calling
thread's ``engine.count`` stack.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Iterable, List, Optional, Sequence, Tuple


@dataclass(frozen=True)
class Span:
    name: str
    tid: int
    start: float          # seconds on the tracer's clock
    end: float

    @property
    def seconds(self) -> float:
        return self.end - self.start


def spans_from_snapshot(events: Sequence[dict]) -> List[Span]:
    """Closed spans from a ``Tracer.snapshot()``; spans still open or
    whose begin fell off the ring are left out."""
    begins = {}
    out: List[Span] = []
    for e in events:
        if e["ph"] == "B":
            begins[e["sid"]] = e
        elif e["ph"] == "E" and e["sid"] in begins:
            b = begins.pop(e["sid"])
            out.append(Span(b["name"], b["tid"], b["ts"] * 1e-6,
                            e["ts"] * 1e-6))
    out.sort(key=lambda s: (s.start, -s.end))
    return out


def union_seconds(intervals: Iterable[Tuple[float, float]]) -> float:
    """Length of the union of ``(start, end)`` intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def inside(spans: Sequence[Span], outer: Span) -> List[Span]:
    """Spans of any thread that lie within ``outer``'s interval."""
    return [s for s in spans if s is not outer
            and s.start >= outer.start and s.end <= outer.end]


def named(spans: Sequence[Span], name: str) -> List[Span]:
    return [s for s in spans if s.name == name]


def seconds_in(spans: Sequence[Span], name: str, outer_name: str) -> float:
    """Total seconds of ``name`` spans inside ``outer_name`` spans."""
    return sum(s.seconds for o in named(spans, outer_name)
               for s in named(inside(spans, o), name))


def wait_before_last(spans: Sequence[Span], inner_name: str,
                     outer_name: str) -> Optional[float]:
    """Seconds on each ``outer_name`` span's own thread, from its start
    to the end of its last ``inner_name`` span, that no ``inner_name``
    span of that thread covers; summed over the outer spans. None when
    no outer span holds an inner one."""
    total, seen = 0.0, False
    for o in named(spans, outer_name):
        inner = [s for s in named(inside(spans, o), inner_name)
                 if s.tid == o.tid]
        if not inner:
            continue
        seen = True
        last = max(s.end for s in inner)
        total += (last - o.start) - union_seconds(
            (s.start, s.end) for s in inner)
    return total if seen else None


def tail_after_last(spans: Sequence[Span], inner_name: str,
                    outer_name: str) -> Optional[float]:
    """Seconds of each ``outer_name`` span after its last ``inner_name``
    span ends; summed. None when no outer span holds an inner one."""
    total, seen = 0.0, False
    for o in named(spans, outer_name):
        inner = named(inside(spans, o), inner_name)
        if inner:
            seen = True
            total += o.end - max(s.end for s in inner)
    return total if seen else None


def open_at(spans: Sequence[Span], t: float) -> List[Span]:
    """Innermost span open at time ``t`` on each thread."""
    best = {}
    for s in spans:
        if s.start <= t < s.end:
            cur = best.get(s.tid)
            if cur is None or s.start >= cur.start:
                best[s.tid] = s
    return list(best.values())
