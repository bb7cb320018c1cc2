"""Arithmetic shared by the metric readers in ``metrics/``. Each reader
returns None where the run holds nothing for it to read."""
from __future__ import annotations

import statistics

import numpy as np


def _median(values) -> float | None:
    values = np.asarray(values, dtype=np.float64)
    values = values[np.isfinite(values)]
    return float(statistics.median(values.tolist())) if len(values) else None


def submit_ms(run) -> float | None:
    """Median host wall time of one ``submit`` (answers back on the
    host), over the submits that started before the traced stretch."""
    rec = run.record
    keep = rec.start < run.untraced_s
    return _median((rec.end[keep] - rec.start[keep]) * 1e3)


def idle_share(run) -> float | None:
    """1 - device busy time / traced stretch, from the trace."""
    if run.trace is None or run.trace.window_s <= 0:
        return None
    return run.trace.idle_share
