#!/usr/bin/env python3
"""What the program's spans and counters cost one engine ``submit`` on
the host, with recording off and on (no profiler running).

    python3 benchmarks/chip/obs_cost.py

Times the calls into ``repro.obs`` that one ``DistanceService.submit``
on an engine makes (``tests/test_obs.py`` pins that span tree: six
spans and three counters), with nothing inside them, and subtracts an
empty loop. Prints one JSON line: nanoseconds per submit over
``REPEATS`` repeats (median and quartiles), off and on, and the host's
CPU. A host-side measurement: it needs no chip.
"""
from __future__ import annotations

import json
import os
import platform
import statistics
import sys
import time

HARNESS = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(HARNESS)),
                                "src"))

LOOPS = 100_000
REPEATS = 9


def one_submit(obs) -> None:
    obs.count("serve.submits")
    with obs.span("repro.submit"):
        with obs.span("repro.plan"):
            obs.count("serve.pairs", 256)
        with obs.span("repro.route"):
            obs.count("serve.pad_pairs", 0)
        with obs.span("repro.dispatch"):
            pass
        with obs.span("repro.fetch"):
            pass
        with obs.span("repro.wrap"):
            pass


def _empty(obs) -> None:
    pass


def per_submit_ns(obs, loops: int) -> list[float]:
    out = []
    for _ in range(REPEATS):
        t = time.perf_counter_ns()
        for _ in range(loops):
            one_submit(obs)
        calls = time.perf_counter_ns() - t
        t = time.perf_counter_ns()
        for _ in range(loops):
            _empty(obs)
        empty = time.perf_counter_ns() - t
        obs.drain()
        out.append((calls - empty) / loops)
    return out


def _summary(values: list[float]) -> dict:
    q1, q2, q3 = statistics.quantiles(values, n=4)
    return {"median": q2, "q1": q1, "q3": q3}


def _cpu() -> str:
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if line.startswith("model name"):
                    return line.split(":", 1)[1].strip()
    except OSError:
        pass
    return platform.processor() or platform.machine()


def main() -> None:
    from repro import obs
    obs.disable()
    obs.reset()
    off = per_submit_ns(obs, LOOPS)
    obs.enable()
    on = per_submit_ns(obs, LOOPS // 10)
    obs.disable()
    obs.reset()
    print(json.dumps({"off_ns_per_submit": _summary(off),
                      "on_ns_per_submit": _summary(on),
                      "cpu": _cpu(), "python": platform.python_version()}))


if __name__ == "__main__":
    main()
