"""Reduce a ``jax.profiler`` trace (``.xplane.pb``) of one measured
window to device busy time, idle share and a breakdown.

- Busy: per device, the union of the intervals of its device operations
  inside the window; ``busy_s`` is the mean over the devices used.
- Idle share: 1 − busy / window.
- ``device_ops``: device self time by operation (less the ops it
  holds, as a loop holds its body), under the names XLA gives them
  (``<module>/<op>``).
- ``idle_gaps``: the longest stretches in which no device ran an
  operation, each labelled with the host spans open at its midpoint.

The window is the host annotation ``bench.window`` that the harness
opens around the timed passes. The harness opens a program span of the
same name at the same moment, which puts the program's spans on the
profiler's clock.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from bench.spans import Span, open_at

WINDOW = "bench.window"
MODULES = "XLA Modules"
_TPU_PLANE = re.compile(r"^/device:TPU:(\d+)$")

# (plane name, line name) -> device index, or None for lines that hold
# no device operations
LineSelector = Callable[[str, str], Optional[int]]


def tpu_ops(plane: str, line: str) -> Optional[int]:
    """The ``XLA Ops`` line of each ``/device:TPU:<i>`` plane."""
    m = _TPU_PLANE.match(plane)
    return int(m.group(1)) if m and line == "XLA Ops" else None


@dataclass
class TraceSummary:
    window_s: float
    busy_s: float                       # mean over the devices used
    busy_by_device: Dict[int, float]
    device_ops: List[Tuple[str, float]] = field(default_factory=list)
    idle_gaps: List[Tuple[str, float]] = field(default_factory=list)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def _stat(event, key: str):
    for k, v in event.stats:
        if k == key:
            return v
    return None


def _op_name(event, module: Optional[str]) -> str:
    """``<module>/<op>``: the TPU names an op by its HLO text
    (``%fusion.5 = s32[...] fusion(...)``), the CPU in stats."""
    op = str(_stat(event, "hlo_op") or event.name).split(" = ")[0]
    module = _stat(event, "hlo_module") or module
    return f"{module}/{op.lstrip('%')}" if module else op.lstrip("%")


def _self_times(events: List[Tuple[float, float, str]]
                ) -> List[Tuple[float, float, str, float]]:
    """(start, end, name, self seconds) of nested ``events``: an op's
    time less that of the ops it holds (a loop holds its body)."""
    out: List[List] = []
    stack: List[int] = []
    for s, e, name in sorted(events, key=lambda x: (x[0], -x[1])):
        while stack and out[stack[-1]][1] <= s:
            stack.pop()
        if stack and e <= out[stack[-1]][1]:
            out[stack[-1]][3] -= e - s
        out.append([s, e, name, e - s])
        stack.append(len(out) - 1)
    return [(s, e, n, t * 1e-9) for s, e, n, t in out]


def _module_at(modules: List[Tuple[float, float, str]], starts, t: float
               ) -> Optional[str]:
    """The module running at ``t``; ``modules`` sorted, not overlapping."""
    i = bisect.bisect_right(starts, t) - 1
    if i >= 0 and t < modules[i][1]:
        return modules[i][2]
    return None


def _merge(intervals: Sequence[Tuple[float, float]]
           ) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def window_of(profile, name: str = WINDOW) -> Tuple[float, float]:
    """(start_ns, end_ns) of the host annotation ``name``."""
    for plane in profile.planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name == name:
                    return float(ev.start_ns), float(ev.end_ns)
    raise ValueError(f"no {name!r} annotation in the trace")


def reduce_profile(profile, *, devices: Sequence[int],
                   host_spans: Sequence[Span] = (),
                   select: LineSelector = tpu_ops,
                   op_filter: Callable = lambda ev: True,
                   top: int = 10) -> TraceSummary:
    """Summarise the window of ``profile`` (a ``jax.profiler.ProfileData``)
    over the device indices ``devices``. ``host_spans`` are on the
    profiler's clock, in seconds. A device with no operation in the
    window reads busy 0."""
    lo, hi = window_of(profile)
    per_dev: Dict[int, List[Tuple[float, float]]] = {d: [] for d in devices}
    by_op: Dict[str, float] = {}
    for plane in profile.planes:
        modules = sorted((float(ev.start_ns), float(ev.end_ns),
                          ev.name.split("(")[0])
                         for line in plane.lines if line.name == MODULES
                         for ev in line.events)
        starts = [m[0] for m in modules]
        for line in plane.lines:
            dev = select(plane.name, line.name)
            if dev is None or dev not in per_dev:
                continue
            ops = []
            for ev in line.events:
                if not op_filter(ev):
                    continue
                s, e = max(float(ev.start_ns), lo), min(float(ev.end_ns), hi)
                if e <= s:
                    continue
                module = _module_at(modules, starts, s)
                ops.append((s, e, _op_name(ev, module)))
            per_dev[dev].extend((s, e) for s, e, _ in ops)
            for _, _, name, secs in _self_times(ops):
                by_op[name] = by_op.get(name, 0.0) + secs
    merged = {d: _merge(iv) for d, iv in per_dev.items()}
    busy = {d: sum(e - s for s, e in m) * 1e-9 for d, m in merged.items()}
    window_s = (hi - lo) * 1e-9
    # idle gaps: stretches of the window in which no device was busy
    gaps, t = [], lo
    for s, e in _merge([iv for m in merged.values() for iv in m]):
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    gaps.sort(key=lambda g: g[0] - g[1])
    labelled = []
    for s, e in gaps[:top]:
        now = [sp.name for sp in open_at(host_spans, (s + e) * 0.5e-9)]
        labelled.append(("+".join(sorted(set(now))) or "no span",
                         (e - s) * 1e-9))
    ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:top]
    return TraceSummary(window_s=window_s,
                        busy_s=sum(busy.values()) / max(1, len(busy)),
                        busy_by_device=busy, device_ops=ops,
                        idle_gaps=labelled)
