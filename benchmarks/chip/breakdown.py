#!/usr/bin/env python3
"""Where the time of ``submit`` and of set-up goes, by the program's own
spans, on the chip.

For each seed, one process deploys the cell with ``repro.obs``
recording, warms it, and drives one traced window as a benchmark run
does (``--seconds``, the profiler on for its last stretch). It then
drives ``--cost-windows`` pairs of untraced windows of
``--cost-seconds`` each, spans off and on in turn, for what recording
costs. The program's state is released and the traced window's answers
are checked as in a benchmark run.

    python3 benchmarks/chip/breakdown.py --workload <cell> \\
        --seeds 11,12 --seconds 30 --cost-windows 2 --cost-seconds 10

Prints one JSON line per seed: the span metrics of the cell (the
``metrics/`` readers of ``chipbench/spans.py``), the harness-clock mean
of the same submits, the split of set-up, the device's idle time
charged to the innermost program span with the estimated host-device
clock offset, the stalls and the span that held each, the cost
windows' median submit times, ``correct`` and the device. Progress goes
to standard error. Not part of the benchmark's own runs.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import statistics  # noqa: E402
import tempfile  # noqa: E402
from collections import Counter  # noqa: E402

from chipbench import host  # noqa: E402

SUBMIT_METRICS = ("route_ms", "dispatch_ms", "fetch_ms", "front_door_ms")
SETUP_METRICS = ("deploy_servers_s", "deploy_center_s")


def metric_names(cell) -> list[str]:
    """The span metrics of ``cell``: the per-submit ones carry its
    traffic's suffix (``trips`` or ``matrix``)."""
    kind = cell.mix["pairs"]["kind"]
    return [f"{m}.{kind}" for m in SUBMIT_METRICS] + list(SETUP_METRICS)


def _span_s(spans, name) -> float:
    return sum(s.end_ns - s.start_ns for s in spans if s.name == name) / 1e9


def _median_ms(rec) -> float:
    return 1e3 * statistics.median((rec.end - rec.start).tolist())


def breakdown(cell, seed: int, seconds: float, cost_windows: int,
              cost_seconds: float, t_start: float, log) -> dict:
    """One seed's reading (module docstring)."""
    from repro import obs

    from chipbench import bench, readers, spans, spantrace

    obs.reset()
    obs.enable()
    run = bench.Run(cell, seed, seconds)
    t0 = time.perf_counter()
    run.deploy()
    t1 = time.perf_counter()
    run.warm()
    t2 = time.perf_counter()
    setup = obs.drain()
    setup_compiles = compiles = obs.counters().get(obs.COMPILES, 0)
    with tempfile.TemporaryDirectory(prefix="chipbench-spans-") as logdir:
        rec = run.window(True, logdir)
        window = obs.drain()
        compiles = obs.counters().get(obs.COMPILES, 0) - compiles
        paths = [os.path.join(d, f) for d, _, fs in os.walk(logdir)
                 for f in fs if f.endswith(".xplane.pb")]
        summary = (spantrace.reduce(spantrace.read_xspace(paths[0]))
                   if paths else None)
    cost = {"off": [], "on": []}
    for k in range(cost_windows):
        for on in ((False, True) if k % 2 == 0 else (True, False)):
            (obs.enable if on else obs.disable)()
            run.seconds = cost_seconds
            cost["on" if on else "off"].append(
                _median_ms(run.window(False)))
            obs.drain()
    run.seconds = seconds
    obs.disable()
    dropped = obs.counters().get(obs.DROPPED, 0)
    device = bench.device_info(cell.chips)
    run.release()
    limits, failed = run.check(rec)
    record = spans.SpanRecord(
        cell, seed, seconds, t2 - t_start, run.net, run.traffic, rec, None,
        device["kind"], run.stretch_start(True), spans=setup + window)
    metrics = {m: cell.reader(m)(record) for m in metric_names(cell)}
    stalls = spans.stall_holders(window, rec)
    build_s = _span_s(setup, "repro.engine.build")
    deploy_s = metrics["deploy_servers_s"] + metrics["deploy_center_s"]
    out = {
        "seed": seed, "correct": all(x.holds for x in limits),
        "failed": failed, "metrics": metrics,
        "harness_ms": spans.harness_ms(record),
        "submit_ms": readers.submit_ms(record),
        "kept_submits": len(spans.kept_submits(record) or ()),
        "setup": {"setup_s": t2 - t_start,
                  "before_deploy_s": t0 - t_start,
                  "generate_s": t1 - t0 - deploy_s,
                  "deploy_center_s": metrics["deploy_center_s"],
                  "deploy_servers_s": metrics["deploy_servers_s"],
                  "engine_build_s": build_s,
                  "warm_s": t2 - t1 - build_s,
                  "compiles": setup_compiles},
        "compiles_in_window": compiles, "dropped_spans": dropped,
        "stalls": [list(x) for x in stalls],
        "stall_tally": dict(Counter(name for *_, name, _ in stalls)),
        "cost_submit_ms": cost, "device": device}
    log(f"compiles in window {compiles}")
    log(f"submits over {bench.STALL_S * 1e3:g} ms: {len(stalls)}; held by "
        + ", ".join(f"{k} {v}" for k, v in out["stall_tally"].items()))
    for at, ms, name, held in stalls[:20]:
        log(f"  stall at {at:.3f} s: {ms:.2f} ms, {name} {held:.2f} ms")
    if summary is not None:
        off = summary.offset
        log(f"offset {'found' if off.found else 'NOT found, 0 used'}: "
            f"delta {off.delta / 1e3:.1f} us in [{off.lo / 1e3:.1f}, "
            f"{off.hi / 1e3:.1f}] us (width {off.width / 1e3:.1f} us) "
            f"from {off.pairs} pairs")
        out["trace"] = {
            "window_s": summary.window_s, "busy_s": summary.busy_s,
            "idle_share": summary.idle_share,
            "offset_ns": {"delta": off.delta, "lo": off.lo, "hi": off.hi,
                          "pairs": off.pairs, "found": off.found},
            "idle_gaps": [[k, v] for k, v in summary.idle_gaps]}
    for x in limits:
        log(("ok   " if x.holds else "FAIL ") + x.text())
    return out


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--cost-windows", type=int, default=2)
    ap.add_argument("--cost-seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    host.setup_environment()
    from chipbench.spec import load_cell

    cell = load_cell(host.ROOT, args.workload, host.HARNESS)
    host.require_chips(cell.chips)
    t_start = T_START
    for seed in (int(s) for s in args.seeds.split(",")):
        out = breakdown(cell, seed, args.seconds, args.cost_windows,
                        args.cost_seconds, t_start, host.log)
        print(json.dumps(out), flush=True)
        t_start = time.perf_counter()


if __name__ == "__main__":
    main()
