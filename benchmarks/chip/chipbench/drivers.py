"""The measured window: drive ``DistanceService.submit`` on the wall
clock.

``closed_loop`` sends the requests of a pool back to back from one
client, on one thread. ``submit`` is the service's ``submit``;
``annotate(name)`` returns a context manager that marks host activity
for the profiler (a no-op outside traced runs); ``stretch`` is an
optional object whose ``poll(now)`` starts and stops the profiler for
the traced part of the window.
"""
from __future__ import annotations

import contextlib
import time
from dataclasses import dataclass

import numpy as np


def no_annotation(name: str):
    return contextlib.nullcontext()


@dataclass
class ClosedLoopRecord:
    """Per request (``submit`` call), in order: its start and end (s
    from window start), the row kept for the check and that row's
    answers. ``t0``: the window's start on ``time.perf_counter``."""
    start: np.ndarray
    end: np.ndarray
    kept_row: np.ndarray
    kept_answers: list[np.ndarray]
    t0: float


def closed_loop(submit, traffic, seconds: float,
                keep_row: np.ndarray, annotate=no_annotation,
                stretch=None) -> ClosedLoopRecord:
    """Requests 0, 1, 2, ... of the pool until ``seconds`` have passed;
    the request in flight at the close is finished and recorded.
    ``traffic`` is a ``MatrixPool`` or a ``TripPool``; ``keep_row[i]``
    is the row of request ``i`` whose answers are kept for the
    check."""
    starts: list[float] = []
    ends: list[float] = []
    rows: list[int] = []
    kept: list[np.ndarray] = []
    width = traffic.width
    clock = time.perf_counter
    t0 = clock()
    i = 0
    while True:
        now = clock() - t0
        if stretch is not None:
            stretch.poll(now)
        if now >= seconds:
            break
        with annotate("bench.client"):
            ss, ts = traffic.request(i)
        with annotate("bench.submit"):
            a = clock()
            out = submit(ss, ts).distances
            b = clock()
        starts.append(a - t0)
        ends.append(b - t0)
        r = int(keep_row[i % len(keep_row)])
        rows.append(r)
        kept.append(out[r * width:(r + 1) * width].copy())
        i += 1
    if stretch is not None:
        stretch.close(clock() - t0)
    return ClosedLoopRecord(np.asarray(starts), np.asarray(ends),
                            np.asarray(rows, dtype=np.int64), kept, t0)
