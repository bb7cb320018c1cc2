"""Arithmetic on the program's own spans (``repro.obs``): self time,
nesting, and the selection of the submits a metric reads.

A span is a ``repro.obs.Span`` or any tuple with its fields: ``name``,
``span_id``, ``parent_id``, ``request_id``, ``start_ns`` and ``end_ns``
on ``time.perf_counter_ns``. The harness's ``ClosedLoopRecord`` keeps
the same clock in seconds, so a ``repro.submit`` span is matched to the
harness's record of the call that holds it.

The readers at the end return None where the run holds no spans, as a
run of a program without ``repro.obs`` does.
"""
from __future__ import annotations

from collections import defaultdict
from dataclasses import dataclass, field

import numpy as np

from .bench import STALL_S, RunRecord

SUBMIT = "repro.submit"
ROUTE = ("repro.route",)
DISPATCH = ("repro.dispatch",)
FETCH = ("repro.fetch",)
# the service's own code around the engine: everything of a submit
# that is not routing, the device call or the fetch
FRONT_DOOR = ("repro.submit", "repro.plan", "repro.wrap", "repro.fold")
DEPLOY_SERVER = "repro.deploy.server"
DEPLOY_CENTER = "repro.deploy.center"


@dataclass
class SpanRecord(RunRecord):
    """A ``RunRecord`` with the spans the program recorded in the run:
    set-up and window."""
    spans: list = field(default_factory=list)


def self_ns(spans) -> dict[int, int]:
    """span id -> duration minus the time its children cover. Children
    of one span run one after another on its thread, so their
    durations add."""
    out = {s.span_id: s.end_ns - s.start_ns for s in spans}
    for s in spans:
        if s.parent_id in out:
            out[s.parent_id] -= s.end_ns - s.start_ns
    return out


def innermost(intervals):
    """Split time among nested ``(start, end, name)`` intervals: yields
    ``(start, end, name)`` segments, each charged to the innermost
    interval open over it. Time that no interval covers is left out.
    An interval that sticks out of the one it starts in is cut at that
    one's end."""
    order = sorted(intervals, key=lambda iv: (iv[0], -iv[1]))
    stack: list[tuple[float, float, str]] = []
    t = None

    def close_until(when):
        nonlocal t
        while stack and stack[-1][1] <= when:
            s, e, n = stack.pop()
            if e > t:
                yield t, e, n
                t = e

    for s, e, n in order:
        yield from close_until(s)
        if stack:
            if s > t:
                yield t, s, stack[-1][2]
            e = min(e, stack[-1][1])
        t = s
        stack.append((s, e, n))
    if stack:
        yield from close_until(float("inf"))


def kept_submits(run) -> list | None:
    """The ``repro.submit`` spans of the calls that ``submit_ms`` reads
    (started before the traced stretch), less those over ``STALL_S``,
    each paired with its harness wall time in seconds: a list of
    ``(span, seconds)``. None without spans."""
    spans = getattr(run, "spans", None)
    if not spans:
        return None
    rec = run.record
    out = []
    for s in spans:
        if s.name != SUBMIT:
            continue
        at = s.start_ns / 1e9 - rec.t0
        i = int(np.searchsorted(rec.start, at, side="right")) - 1
        if i < 0 or s.end_ns / 1e9 - rec.t0 > rec.end[i]:
            continue                    # not a call of the window
        took = float(rec.end[i] - rec.start[i])
        if rec.start[i] < run.untraced_s and took <= STALL_S:
            out.append((s, took))
    return out


def mean_self_ms(run, names) -> float | None:
    """Mean, over the kept submits, of the self time of the spans named
    ``names`` in each submit's tree, in ms."""
    kept = kept_submits(run)
    if not kept:
        return None
    wanted = {s.span_id for s, _ in kept}
    own = self_ns(run.spans)
    total = sum(own[s.span_id] for s in run.spans
                if s.request_id in wanted and s.name in names)
    return total / len(kept) / 1e6


def harness_ms(run) -> float | None:
    """Mean harness wall time of the kept submits, in ms: what the
    four per-submit means add up to, less the call into ``submit``."""
    kept = kept_submits(run)
    if not kept:
        return None
    return 1e3 * sum(t for _, t in kept) / len(kept)


def route_ms(run):
    return mean_self_ms(run, ROUTE)


def dispatch_ms(run):
    return mean_self_ms(run, DISPATCH)


def fetch_ms(run):
    return mean_self_ms(run, FETCH)


def front_door_ms(run):
    return mean_self_ms(run, FRONT_DOOR)


def _durations_s(run, name) -> list[float] | None:
    spans = getattr(run, "spans", None)
    if not spans:
        return None
    got = [(s.end_ns - s.start_ns) / 1e9 for s in spans if s.name == name]
    return got or None


def deploy_servers_s(run) -> float | None:
    """Sum of the ``repro.deploy.server`` spans: every district's
    index build and shortcut install."""
    got = _durations_s(run, DEPLOY_SERVER)
    return None if got is None else sum(got)


def deploy_center_s(run) -> float | None:
    """The ``repro.deploy.center`` span: the border-label build."""
    got = _durations_s(run, DEPLOY_CENTER)
    return None if got is None else sum(got)


def stall_holders(spans, rec, stall_s: float = STALL_S):
    """For each ``repro.submit`` over ``stall_s`` on the harness's clock:
    (start in the window s, ms, the span of its tree with the most self
    time, that span's self ms). The submit's own self time competes as
    ``repro.submit``."""
    by_request = defaultdict(list)
    for s in spans:
        by_request[s.request_id].append(s)
    own = self_ns(spans)
    out = []
    for s in spans:
        if s.name != SUBMIT:
            continue
        at = s.start_ns / 1e9 - rec.t0
        i = int(np.searchsorted(rec.start, at, side="right")) - 1
        if i < 0:
            continue
        took = float(rec.end[i] - rec.start[i])
        if took <= stall_s:
            continue
        holder = max(by_request[s.span_id], key=lambda x: own[x.span_id])
        out.append((float(rec.start[i]), 1e3 * took, holder.name,
                    own[holder.span_id] / 1e6))
    return out
