"""Readers of the four-chip serving step in a device trace.

The step is the jitted ``shard_map`` of ``edge/sharded_oracle.py``.
``STEP_JITS`` are the jit names the trace's module line is searched
for: the step's own name and the generic name the same step carried
before it had one, so that both sides of a comparison read.

Its collectives are all-reduces: the ``pmin`` that assembles the border
rows (B row-sharded) and the one that gathers the answers. The compiled
step names each after the ``pmin`` it lowers (``pmin.14 =
s32[2048,1101] all-reduce(...)`` in the step compiled for a v5e 2x2);
``all-reduce`` names, and their async start and done halves, match too.
Ops are read from the trace's largest ten (``TraceSummary.device_ops``),
so ``collective_us`` reads only when both all-reduces are among them:
one pushed off the list by larger ops reads None, and fails the run,
rather than a smaller number that would pass for a gain.

Module and op times are summed over the chips, so each ratio below is
per step and per chip. Each reader returns None where the trace holds
nothing for it.
"""
from __future__ import annotations

import re

STEP_JITS = ("jit__sharded_step", "jit__device_fn")
COLLECTIVE = re.compile(r"^(pmin|all-reduce)([.-]|$)")
# the two halves of one async collective share a name but for this
ASYNC_HALF = re.compile(r"-(start|done)")
# the step's all-reduces with B row-sharded: border rows and answers
COLLECTIVES = 2


def _step(run) -> tuple[float, int]:
    """(device seconds, executions) of the step, summed over chips."""
    if run.trace is None:
        return 0.0, 0
    return run.trace.program_time(STEP_JITS)


def step_us(run) -> float | None:
    """Device microseconds per execution of the step."""
    seconds, steps = _step(run)
    return 1e6 * seconds / steps if steps and seconds > 0 else None


def collective_us(run) -> float | None:
    """Device microseconds of the step's all-reduces per execution; None
    unless all ``COLLECTIVES`` of them are in the trace's op list."""
    _, steps = _step(run)
    if not steps:
        return None
    found: dict[str, float] = {}
    for name, seconds in run.trace.device_ops:
        if COLLECTIVE.match(name):
            key = ASYNC_HALF.sub("", name)
            found[key] = found.get(key, 0.0) + seconds
    total = sum(found.values())
    if len(found) < COLLECTIVES or total <= 0:
        return None
    return 1e6 * total / steps
