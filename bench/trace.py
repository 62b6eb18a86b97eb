"""Reduction of a profiler trace to device intervals.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes and keeps,
for each TPU, the events of its "XLA Ops" line (one operation at a time on
the TensorCore) and of its "XLA Modules" line (one event per program run),
and every host event that has a duration. Times stay in nanoseconds on the
profiler's own clock, which host and device planes share.

An op's name is the HLO instruction's name (``fusion.310``; the trace
gives the whole instruction text). Ops can nest, as a ``while`` holds the
ops of its body: busy time is the union of all of them, and the per-op
breakdown and the collective arithmetic use only the innermost ones.

The functions below are the whole arithmetic of the trace metrics:
intervals are merged into their union, and a span of the window is busy
where an operation runs.
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]

_DEVICE = re.compile(r"^/device:TPU:(\d+)$")
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all")


@dataclass
class Event:
    name: str
    start: float
    end: float


@dataclass
class Trace:
    ops: Dict[int, List[Event]] = field(default_factory=dict)
    modules: Dict[int, List[Event]] = field(default_factory=dict)
    host: List[Event] = field(default_factory=list)


def op_name(text: str) -> str:
    """``fusion.310`` of ``%fusion.310 = s32[...] fusion(...)``."""
    return text.split(" = ", 1)[0].lstrip("%") if " = " in text else text


def innermost(events: List[Event]) -> List[Event]:
    """The events of a line that hold no other event of it."""
    order = sorted(events, key=lambda e: (e.start, -e.end))
    outer = set()
    stack: List[int] = []
    for i, e in enumerate(order):
        while stack and order[stack[-1]].end <= e.start:
            stack.pop()
        if stack and order[stack[-1]].end >= e.end:
            outer.add(stack[-1])
        stack.append(i)
    return [e for i, e in enumerate(order) if i not in outer]


def from_profile(profile) -> Trace:
    """A ``jax.profiler.ProfileData`` as a ``Trace``."""
    tr = Trace()
    for plane in profile.planes:
        m = _DEVICE.match(plane.name)
        for line in plane.lines:
            if m:
                dev = int(m.group(1))
                if line.name == "XLA Ops":
                    dest = tr.ops.setdefault(dev, [])
                elif line.name == "XLA Modules":
                    dest = tr.modules.setdefault(dev, [])
                else:
                    continue
            elif plane.name.startswith("/host:"):
                dest = tr.host
            else:
                continue
            for e in line.events:
                if e.duration_ns > 0:
                    dest.append(Event(op_name(e.name), float(e.start_ns),
                                      float(e.start_ns + e.duration_ns)))
    for evs in list(tr.ops.values()) + list(tr.modules.values()):
        evs.sort(key=lambda e: e.start)
    tr.host.sort(key=lambda e: e.start)
    return tr


def load(trace_dir: str) -> Trace:
    """The newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return from_profile(ProfileData.from_file(max(paths, key=os.path.getmtime)))


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Merged, sorted, non-overlapping cover of ``intervals``."""
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def covered(intervals: Sequence[Interval], lo: float, hi: float) -> float:
    """Length of [lo, hi] that the merged ``intervals`` cover."""
    return sum(max(0.0, min(b, hi) - max(a, lo)) for a, b in intervals)


def subtract(intervals: Sequence[Interval], cut: Sequence[Interval]
             ) -> List[Interval]:
    """Parts of merged ``intervals`` that merged ``cut`` does not cover."""
    out: List[Interval] = []
    j = 0
    for a, b in intervals:
        while j < len(cut) and cut[j][1] <= a:
            j += 1
        k, s = j, a
        while k < len(cut) and cut[k][0] < b:
            if cut[k][0] > s:
                out.append((s, cut[k][0]))
            s = max(s, cut[k][1])
            k += 1
        if s < b:
            out.append((s, b))
    return out


@dataclass
class DeviceWindow:
    """One device over the traced steps: from the start of the first step
    program to the end of the last."""
    steps: List[Event]
    busy: List[Interval]          # union of every op interval in the window
    ops: List[Event]              # the innermost ops in the window

    @property
    def lo(self) -> float:
        return self.steps[0].start

    @property
    def hi(self) -> float:
        return self.steps[-1].end

    @property
    def window_ns(self) -> float:
        return self.hi - self.lo

    @property
    def busy_ns(self) -> float:
        return covered(self.busy, self.lo, self.hi)


def device_windows(tr: Trace, step_pattern: str) -> Dict[int, DeviceWindow]:
    """Per device, the window spanned by the program runs whose name matches
    ``step_pattern``; devices that ran none are left out."""
    pat = re.compile(step_pattern)
    out = {}
    for dev, mods in sorted(tr.modules.items()):
        steps = [e for e in mods if pat.search(e.name)]
        if not steps:
            continue
        lo, hi = steps[0].start, steps[-1].end
        ops = [e for e in tr.ops.get(dev, []) if e.end > lo and e.start < hi]
        if not ops:      # no op line: the program runs stand for the ops
            ops = [e for e in mods if e.end > lo and e.start < hi]
        out[dev] = DeviceWindow(steps, union((e.start, e.end) for e in ops),
                                innermost(ops))
    return out


def step_period_ns(w: DeviceWindow) -> Optional[float]:
    """Mean time from the start of one step program to the next."""
    if len(w.steps) < 2:
        return None
    return (w.steps[-1].start - w.steps[0].start) / (len(w.steps) - 1)


def gap_idle_ns(w: DeviceWindow) -> List[float]:
    """Idle time of the device between each pair of consecutive steps."""
    return [(b.start - a.end) - covered(w.busy, a.end, b.start)
            for a, b in zip(w.steps, w.steps[1:])]


def exposed_collective_ns(w: DeviceWindow) -> float:
    """Time in the window in which a collective op runs and no other op."""
    coll = union((e.start, e.end) for e in w.ops if COLLECTIVE.search(e.name))
    other = union((e.start, e.end) for e in w.ops
                  if not COLLECTIVE.search(e.name))
    return sum(b - a for a, b in subtract(coll, other))


def top_ops(windows: Dict[int, DeviceWindow], n: int = 10
            ) -> List[Tuple[str, float]]:
    """The ``n`` op names with the most device time in the window, in
    seconds per device."""
    total: Dict[str, float] = {}
    for w in windows.values():
        for e in w.ops:
            d = min(e.end, w.hi) - max(e.start, w.lo)
            if d > 0:
                total[e.name] = total.get(e.name, 0.0) + d
    k = max(1, len(windows))
    ranked = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [(name, ns / k / 1e9) for name, ns in ranked]


def idle_gaps(tr: Trace, w: DeviceWindow, n: int = 10,
              skip: Sequence[str] = ()) -> List[Tuple[str, float]]:
    """The ``n`` longest idle gaps of one device in its window, in seconds,
    each named by the host event that overlaps it most (names in ``skip``,
    such as the span around the whole window, are passed over)."""
    gaps = subtract([(w.lo, w.hi)], w.busy)
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for a, b in gaps[:n]:
        best, best_ov = "no host event", 0.0
        for e in tr.host:
            if e.start >= b:
                break
            ov = min(e.end, b) - max(e.start, a)
            if ov > best_ov and e.name not in skip:
                best, best_ov = e.name, ov
        out.append((best, (b - a) / 1e9))
    return out
