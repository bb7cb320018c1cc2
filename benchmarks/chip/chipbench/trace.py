"""Reduction of a profiler trace to device busy time, idle share,
per-program device time and the breakdown of where time went.

A trace is reduced from a flat list of ``Event``s, so the arithmetic
can be checked on a hand-made list as well as on a recorded trace:

* device events are those on the ``XLA Ops`` line of a ``/device:TPU:``
  plane; busy time is the union of their intervals inside the traced
  stretch, averaged over the chips that ran something;
* program (jit) device time is the sum of the durations of events on
  the ``XLA Modules`` line whose name starts with a given jit name;
* host activity is read from the benchmark's own annotations
  (``bench.*`` events on host planes), and each idle gap on the device
  is charged to the annotation that overlaps it most.

On a TPU v5e the device's events are stamped up to about a millisecond
earlier than the host span that dispatched them (the recorded trace in
``tests/data`` shows it), so the charge of gaps shorter than a few
milliseconds to host activity is rough; busy time over a stretch of
seconds is not affected.
"""
from __future__ import annotations

import bisect
import re
from collections import defaultdict
from dataclasses import dataclass

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PREFIX = "bench."
STRETCH = "bench.stretch"
UNTRACKED = "untracked"


@dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def read_xspace(path: str) -> list[Event]:
    """The events of an ``.xplane.pb`` file that the reduction reads:
    the device planes' op and module lines and the benchmark's host
    annotations."""
    from jax.profiler import ProfileData

    events = []
    for plane in ProfileData.from_file(path).planes:
        device = DEVICE_PLANE.match(plane.name) is not None
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for ev in line.events:
                if not device and not ev.name.startswith(HOST_PREFIX):
                    continue
                events.append(Event(plane.name, line.name, ev.name,
                                    float(ev.start_ns),
                                    float(ev.duration_ns)))
    return events


def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    """Merge (start, end) intervals into disjoint sorted ones."""
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _clip(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


@dataclass
class TraceSummary:
    """What the reduction found in one traced stretch."""
    window_s: float
    busy_s: float                       # averaged over chips
    chips: int
    device_ops: list[tuple[str, float]]     # name, total seconds
    idle_gaps: list[tuple[str, float]]      # host activity, idle seconds
    modules: dict[str, tuple[float, int]]   # name -> (seconds, count)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def program_time(self, jit_names) -> tuple[float, int]:
        """(device seconds, executions) of the programs whose module
        name starts with one of ``jit_names``."""
        seconds, count = 0.0, 0
        for name, (s, c) in self.modules.items():
            if any(_module_base(name) == j for j in jit_names):
                seconds += s
                count += c
        return seconds, count


def op_name(text: str) -> str:
    """An op event's name is its HLO instruction; keep the name it
    defines: ``%fusion.1 = u16[...] fusion(...)`` -> ``fusion.1``."""
    return text.split(" = ", 1)[0].lstrip("%").strip()


def _module_base(name: str) -> str:
    """``jit_step(12)`` -> ``jit_step``."""
    return name.split("(", 1)[0].strip()


def reduce(events: list[Event], top: int = 10) -> TraceSummary:
    """Reduce the events of one traced stretch (see module docstring).
    The stretch is the ``bench.stretch`` host annotation; without it,
    the span of all events."""
    host = [e for e in events if DEVICE_PLANE.match(e.plane) is None
            and e.name.startswith(HOST_PREFIX)]
    stretch = [e for e in host if e.name == STRETCH]
    if stretch:
        lo = min(e.start_ns for e in stretch)
        hi = max(e.end_ns for e in stretch)
    else:
        lo = min(e.start_ns for e in events)
        hi = max(e.end_ns for e in events)
    window_ns = hi - lo
    ops_by_plane: dict[str, list[tuple[float, float]]] = defaultdict(list)
    op_time: dict[str, float] = defaultdict(float)
    modules: dict[str, list[float]] = defaultdict(lambda: [0.0, 0])
    for e in events:
        if DEVICE_PLANE.match(e.plane) is None:
            continue
        inside = _clip([(e.start_ns, e.end_ns)], lo, hi)
        if not inside:
            continue
        a, b = inside[0]
        if e.line == OPS_LINE:
            ops_by_plane[e.plane].append((a, b))
            op_time[op_name(e.name)] += (b - a) / 1e9
        elif e.line == MODULES_LINE:
            modules[e.name][0] += (b - a) / 1e9
            modules[e.name][1] += 1
    busy_by_plane = {p: union(iv) for p, iv in ops_by_plane.items()}
    chips = max(1, len(busy_by_plane))
    busy_ns = sum(b - a for iv in busy_by_plane.values() for a, b in iv)
    activity = sorted((e.start_ns, e.end_ns, e.name) for e in host
                      if e.name != STRETCH)
    idle: dict[str, float] = defaultdict(float)
    for iv in busy_by_plane.values() or [[]]:
        for a, b in _gaps(iv, lo, hi):
            idle[_charged_to(a, b, activity)] += (b - a) / 1e9 / chips
    return TraceSummary(
        window_s=window_ns / 1e9, busy_s=busy_ns / 1e9 / chips,
        chips=chips,
        device_ops=sorted(op_time.items(), key=lambda kv: -kv[1])[:top],
        idle_gaps=sorted(idle.items(), key=lambda kv: -kv[1])[:top],
        modules={k: (v[0], int(v[1])) for k, v in modules.items()})


def _gaps(busy: list[tuple[float, float]], lo: float, hi: float):
    t = lo
    for a, b in busy:
        if a > t:
            yield t, a
        t = max(t, b)
    if hi > t:
        yield t, hi


def _charged_to(a: float, b: float, activity) -> str:
    """The host annotation that overlaps [a, b] most. ``activity`` is
    sorted by start; annotations of the one benchmark thread do not
    nest, so the walk back from ``b`` stops at the first one that
    ended before ``a``."""
    best, name = 0.0, UNTRACKED
    i = bisect.bisect_left(activity, (b,)) - 1
    while i >= 0:
        s, e, n = activity[i]
        if e <= a:
            break
        overlap = min(b, e) - max(a, s)
        if overlap > best:
            best, name = overlap, n
        i -= 1
    return name
