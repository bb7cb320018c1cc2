"""Where the device's idle time went, by the program's own spans.

The reduction in ``trace.py`` charges idle gaps to the benchmark's
``bench.*`` annotations only. This one reads the program's ``repro.*``
spans beside them (``repro.obs`` puts each span into the profiler trace
as a ``TraceAnnotation`` while it records) and charges each instant of
an idle gap to the innermost span open at that instant, so that
``bench.submit`` keeps only what no program span inside it covers.

The device stamps its events on another clock than the host's spans:
on a TPU v5e a step's module can start before the host span that
dispatched it. So the offset δ (device time + δ = host time) is
estimated first. Each engine step module on the ``XLA Modules`` line
is paired, in order, with the ``repro.dispatch`` span that launched it
and the ``repro.fetch`` span that waited for it; each pair bounds δ:

    dispatch.start <= module.start + δ   and   module.end + δ <= fetch.end

The bounds are intersected over the stretch and δ is the midpoint. An
empty intersection falls back to δ = 0, and the summary says so.
"""
from __future__ import annotations

from collections import defaultdict
from dataclasses import dataclass

from . import trace
from .spans import innermost
from .trace import DEVICE_PLANE, MODULES_LINE, OPS_LINE, STRETCH, Event

PROGRAM_PREFIX = "repro."
DISPATCH = "repro.dispatch"
FETCH = "repro.fetch"
# the jit names of the engines' steps
STEP_JITS = ("jit__engine_fn", "jit__engine_fn_quantized")


def read_xspace(path: str) -> list[Event]:
    """The events of an ``.xplane.pb`` file that ``reduce`` reads: the
    device planes' op and module lines and the host's ``bench.*`` and
    ``repro.*`` annotations."""
    from jax.profiler import ProfileData

    events = []
    for plane in ProfileData.from_file(path).planes:
        device = DEVICE_PLANE.match(plane.name) is not None
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for ev in line.events:
                if not device and not ev.name.startswith(
                        (trace.HOST_PREFIX, PROGRAM_PREFIX)):
                    continue
                events.append(Event(plane.name, line.name, ev.name,
                                    float(ev.start_ns),
                                    float(ev.duration_ns)))
    return events


@dataclass
class Offset:
    """Host-minus-device clock offset, ns: the midpoint of ``[lo, hi]``,
    from ``pairs`` module/span pairs; ``found`` is False where the
    bounds did not intersect (then ``delta`` is 0)."""
    delta: float
    lo: float
    hi: float
    pairs: int
    found: bool

    @property
    def width(self) -> float:
        return self.hi - self.lo


def _is_step(name: str) -> bool:
    return name.split("(", 1)[0].strip() in STEP_JITS


def estimate_offset(events: list[Event]) -> Offset:
    """δ from the pairs of engine step modules and ``repro.dispatch`` /
    ``repro.fetch`` spans (module docstring). Modules of every chip are
    paired with the same spans."""
    host = [e for e in events if DEVICE_PLANE.match(e.plane) is None]
    dispatch = sorted((e for e in host if e.name == DISPATCH),
                      key=lambda e: e.start_ns)
    fetch = sorted((e for e in host if e.name == FETCH),
                   key=lambda e: e.start_ns)
    # the fetch of each dispatch: the first to start after it ends
    calls, j = [], 0
    for d in dispatch:
        while j < len(fetch) and fetch[j].start_ns < d.end_ns:
            j += 1
        if j == len(fetch):
            break
        calls.append((d.start_ns, fetch[j].end_ns))
        j += 1
    by_plane = defaultdict(list)
    for e in events:
        if e.line == MODULES_LINE and _is_step(e.name) \
                and DEVICE_PLANE.match(e.plane):
            by_plane[e.plane].append(e)
    lo, hi, pairs = float("-inf"), float("inf"), 0
    for modules in by_plane.values():
        modules.sort(key=lambda e: e.start_ns)
        for m, (start, end) in zip(modules, calls):
            lo = max(lo, start - m.start_ns)
            hi = min(hi, end - m.end_ns)
            pairs += 1
    if pairs == 0 or lo > hi:
        return Offset(0.0, lo, hi, pairs, False)
    return Offset((lo + hi) / 2, lo, hi, pairs, True)


@dataclass
class SpanSummary:
    """What the reduction found in one traced stretch, on the host's
    clock."""
    window_s: float
    busy_s: float                       # averaged over chips
    chips: int
    offset: Offset
    idle_gaps: list[tuple[str, float]]  # innermost span, idle seconds

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def reduce(events: list[Event], top: int = 12) -> SpanSummary | None:
    """Busy time and the charge of idle gaps to the innermost host span,
    with the device's events moved by the estimated offset, inside the
    ``bench.stretch`` annotation. None where the trace holds no device
    op or no stretch."""
    offset = estimate_offset(events)
    delta = offset.delta
    host = [e for e in events if DEVICE_PLANE.match(e.plane) is None]
    stretch = [e for e in host if e.name == STRETCH]
    ops_by_plane: dict[str, list[tuple[float, float]]] = defaultdict(list)
    for e in events:
        if e.line == OPS_LINE and DEVICE_PLANE.match(e.plane):
            ops_by_plane[e.plane].append((e.start_ns + delta,
                                          e.end_ns + delta))
    if not ops_by_plane or not stretch:
        return None
    lo = min(e.start_ns for e in stretch)
    hi = max(e.end_ns for e in stretch)
    busy = {p: trace.union(trace._clip(iv, lo, hi))
            for p, iv in ops_by_plane.items()}
    chips = len(busy)
    busy_ns = sum(b - a for iv in busy.values() for a, b in iv)
    segments = list(innermost((e.start_ns, e.end_ns, e.name)
                              for e in host if e.name != STRETCH))
    idle: dict[str, float] = defaultdict(float)
    for iv in busy.values():
        gaps = list(trace._gaps(iv, lo, hi))
        for name, ns in charge(gaps, segments).items():
            idle[name] += ns / 1e9 / chips
    return SpanSummary(
        window_s=(hi - lo) / 1e9, busy_s=busy_ns / 1e9 / chips,
        chips=chips, offset=offset,
        idle_gaps=sorted(idle.items(), key=lambda kv: -kv[1])[:top])


def charge(gaps, segments) -> dict[str, float]:
    """ns of the ``(start, end)`` gaps under each name of the sorted,
    disjoint ``(start, end, name)`` segments; the rest under
    ``trace.UNTRACKED``."""
    out: dict[str, float] = defaultdict(float)
    j = 0
    for a, b in gaps:
        while j < len(segments) and segments[j][1] <= a:
            j += 1
        covered = 0.0
        k = j
        while k < len(segments) and segments[k][0] < b:
            s, e, name = segments[k]
            overlap = min(b, e) - max(a, s)
            if overlap > 0:
                out[name] += overlap
                covered += overlap
            k += 1
        if b - a > covered:
            out[trace.UNTRACKED] += b - a - covered
    return out
