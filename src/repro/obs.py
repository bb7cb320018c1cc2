"""Spans and counters of the serving and deployment paths.

Recording is off by default. ``enable()`` switches it on for the
process, ``disable()`` off again, and ``reset()`` forgets what was
recorded. While it is off, ``span`` returns one shared no-op context
and records nothing.

* ``span(name, **attrs)`` is a context manager. On exit it records a
  ``Span``: name, span id, parent id, request id (the id of the
  outermost open span of the thread: on the serving path, the
  ``repro.submit`` span), and start and end on
  ``time.perf_counter_ns``, plus the keyword ``attrs``. Spans are kept
  in memory, up to ``CAPACITY`` of them; past that, each lost span
  counts in ``obs.dropped_spans``. ``drain()`` hands them over and
  empties the buffer. While recording is on and a profiler trace is
  being taken, each span also enters
  ``jax.profiler.TraceAnnotation(name)``, so that the trace holds the
  same spans on the device trace's clock.
* ``count(name, n=1)`` adds to a named integer counter, on or off;
  ``counters()`` returns a copy. ``xla.compiles`` counts XLA backend
  compilations, from a ``jax.monitoring`` listener.

Every span name starts with ``repro.``. The span tree of one
``DistanceService.submit`` on an engine, and the counters, are listed
in docs/ARCHITECTURE.md ("Tracing"). Counters take no lock: they are
exact when one thread serves.
"""
from __future__ import annotations

import itertools
import threading
import time
from collections import defaultdict
from typing import NamedTuple

from jax import monitoring
from jax.profiler import TraceAnnotation

# spans kept between two drains; a 30 s window of 256-pair submits
# records about 150,000
CAPACITY = 1 << 20
DROPPED = "obs.dropped_spans"
COMPILES = "xla.compiles"
_BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class Span(NamedTuple):
    name: str
    span_id: int
    parent_id: int          # 0 for an outermost span
    request_id: int         # span id of the outermost enclosing span
    start_ns: int
    end_ns: int
    attrs: dict | None


class _NoSpan:
    """The context ``span`` returns while recording is off."""
    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, exc_type, exc, tb):
        return None


NO_SPAN = _NoSpan()

# the recorder's state: one per process, as the switch is
_on = False
_spans: list[tuple] = []
_counters: defaultdict[str, int] = defaultdict(int)
_ids = itertools.count(1)
_local = threading.local()              # .stack: the open spans


class _OpenSpan:
    __slots__ = ("name", "attrs", "span_id", "parent_id", "request_id",
                 "start_ns", "_stack", "_mark")

    def __init__(self, name: str, attrs: dict | None):
        self.name = name
        self.attrs = attrs

    def __enter__(self):
        try:
            stack = _local.stack
        except AttributeError:
            stack = _local.stack = []
        self._stack = stack
        self.span_id = next(_ids)
        if stack:
            self.parent_id = stack[-1].span_id
            self.request_id = stack[-1].request_id
        else:
            self.parent_id = 0
            self.request_id = self.span_id
        stack.append(self)
        # only a running profiler records the annotation
        if TraceAnnotation.is_enabled():
            self._mark = TraceAnnotation(self.name)
            self._mark.__enter__()
        else:
            self._mark = None
        self.start_ns = time.perf_counter_ns()
        return self

    def __exit__(self, exc_type, exc, tb):
        end_ns = time.perf_counter_ns()
        if self._mark is not None:
            self._mark.__exit__(exc_type, exc, tb)
        self._stack.pop()
        if len(_spans) < CAPACITY:
            # a plain tuple here; drain() makes it a Span
            _spans.append((self.name, self.span_id, self.parent_id,
                           self.request_id, self.start_ns, end_ns,
                           self.attrs))
        else:
            _counters[DROPPED] += 1
        return None


def span(name: str, **attrs):
    """A context that records one span while recording is on (see the
    module docstring), and the shared no-op ``NO_SPAN`` while it is
    off."""
    if not _on:
        return NO_SPAN
    return _OpenSpan(name, attrs or None)


def count(name: str, n: int = 1) -> None:
    """Add ``n`` to the counter ``name``."""
    _counters[name] += n


def counters() -> dict[str, int]:
    return dict(_counters)


def enable() -> None:
    """Record spans from now on."""
    global _on
    _on = True


def disable() -> None:
    """Stop recording spans; spans still open are recorded when they
    close. What was recorded stays until ``drain`` or ``reset``."""
    global _on
    _on = False


def drain() -> list[Span]:
    """The spans recorded since the last drain or reset, in the order
    they closed; the buffer is emptied."""
    global _spans
    spans, _spans = _spans, []
    return list(map(Span._make, spans))


def reset() -> None:
    """Forget every recorded span and zero every counter."""
    global _spans
    _spans = []
    _counters.clear()


def _on_event_duration(event: str, duration: float, **kw) -> None:
    if event == _BACKEND_COMPILE_EVENT:
        _counters[COMPILES] += 1


monitoring.register_event_duration_secs_listener(_on_event_duration)
