"""Open-loop load sweep: goodput vs offered load at up to millions of
simulated clients, through the real ``DistanceService``.

Five sections, all on one deployed 40×40 grid (8 districts):

1. **Goodput curve** — offered load swept as multiples of the measured
   single-server capacity (one warm batch dispatch), unbounded queue:
   under overload (x ≥ 1) the queue grows without bound and p99/p999
   blow up while goodput saturates at capacity.
2. **Bounded-queue drop policy** — same overload points with
   ``max_queue`` set: arrivals beyond the bound are shed, goodput holds
   at capacity, and the p99 of *admitted* requests stays bounded by the
   queue depth.
3. **Traffic shapes** — diurnal and flash-crowd profiles at a fixed
   sub-capacity offered load: the flash crowd's 8× burst is the tail
   event the mean-rate curve hides.
4. **Rebuild-window policies** — a §5 rebuild window opened mid-run
   (shortcut push withheld): ``stale_ok`` keeps serving (bounded
   staleness as admission control, ``stale_frac`` > 0, flat tail)
   versus ``certify_or_wait`` where uncertified queries pay the
   measured shortcut-push wait inside the service time.
5. **Open vs closed loop** — the same target load issued open loop and
   by 32 blocking clients: the closed fleet self-throttles, so its
   offered load and p99 hide the queue the open-loop run exposes.

The million-client point (section 1) is the ROADMAP's north-star
workload: ≥ 10⁶ simulated clients in one run, queue-delay-inclusive
p50/p99/p999 recorded.  ``--quick`` trims the curve but keeps that
point — the committed ``BENCH_PR<N>.json`` baseline is produced with
``--quick`` (see benchmarks/README section in the main README).
"""
from __future__ import annotations

import argparse
import time

import numpy as np

from .common import emit, timeit

BATCH = 1024
WINDOW_MS = 2.0
HORIZON_MS = 2_000.0
PER_CLIENT_QPS = 0.5
CURVE_MULTS = (0.25, 0.5, 0.8, 1.5)
QUICK_CURVE_MULTS = (0.5, 1.5)
DROP_MULTS = (1.5, 3.0)
QUICK_DROP_MULTS = (3.0,)
MAX_QUEUE = 8 * BATCH
SHAPES = ("diurnal", "flash_crowd")
MEGA_CLIENTS = 1_000_000


def _report(tag: str, rep, extra: str = "") -> None:
    cfg = rep.row()
    emit(f"load/{tag}/goodput", rep.goodput_qps, unit="qps",
         derived=f"offered_qps={rep.offered_qps:,.0f}"
                 f";clients={rep.num_clients:,}{extra}", config=cfg)
    emit(f"load/{tag}/p50", rep.p50_ms, unit="ms",
         derived=f"mean={rep.mean_ms:.2f}ms", config=None)
    emit(f"load/{tag}/p99", rep.p99_ms, unit="ms",
         derived=f"p999={rep.p999_ms:.2f}ms;max={rep.max_ms:.2f}ms",
         config=None)
    emit(f"load/{tag}/p999", rep.p999_ms, unit="ms", config=None)
    emit(f"load/{tag}/shed-frac", rep.shed_frac, unit="info",
         derived=f"shed={rep.shed:,};queue_peak={rep.queue_peak:,}",
         config=None)
    emit(f"load/{tag}/stale-frac", rep.stale_frac, unit="info",
         derived=f"certified_frac={rep.certified_frac:.3f}", config=None)


def _clients_for(offered_qps: float) -> int:
    return max(1, int(round(offered_qps / PER_CLIENT_QPS)))


def run(quick: bool = False) -> None:
    from repro.core import grid_partition, grid_road_network
    from repro.serve import (OpenLoopLoadGen, ServingPolicy,
                             close_rebuild_window)
    from repro.serve.service import CERTIFY_OR_WAIT, STALE_OK
    from repro.update.scenarios import scenario_weights
    from repro.edge import EdgeSystem
    from repro.serve.loadgen import open_rebuild_window

    g = grid_road_network(40, 40, seed=11)
    part = grid_partition(g, 40, 40, 2, 4)
    system = EdgeSystem.deploy(g, part)
    service = system.service(ServingPolicy(rebuild=STALE_OK))
    gen = OpenLoopLoadGen(service, batch_size=BATCH, window_ms=WINDOW_MS,
                          seed=0)
    gen.warmup()

    # measured capacity: queries/s of one warm full-batch dispatch
    zeros = np.zeros(BATCH, dtype=np.int64)
    real = np.zeros(BATCH, dtype=bool)
    _, sec = timeit(lambda: service.submit(zeros, zeros, real=real),
                    repeats=5)
    cap_qps = BATCH / sec
    emit("load/capacity", cap_qps, unit="qps",
         derived=f"batch={BATCH};us_per_query={sec / BATCH * 1e6:.3f}")
    # resident footprint of the serving plane (deterministic — the row
    # the telemetry bytes gate actually watches in the quick profile)
    plane = service.plan(zeros, zeros).plane
    emit("load/engine-resident-bytes", plane.size_bytes(), unit="bytes",
         derived=f"plane={type(plane).__name__};n={g.num_vertices}")

    horizon = HORIZON_MS / 2 if quick else HORIZON_MS

    # 1. goodput curve, unbounded queue
    for mult in (QUICK_CURVE_MULTS if quick else CURVE_MULTS):
        offered_qps = mult * cap_qps
        rep = gen.run(_clients_for(offered_qps), PER_CLIENT_QPS, horizon)
        _report(f"open-x{mult:g}", rep)

    # million-client north-star point (kept in --quick: the acceptance
    # workload).  Aggregate offered rate ≈ 0.7 × capacity so the queue
    # is busy but the run measures service, not an unbounded backlog;
    # the horizon is sized for ≈ 1.05e6 arrivals (Poisson σ ≈ 1e3, so
    # the 10⁶ floor holds with overwhelming probability).
    per_client = 0.7 * cap_qps / MEGA_CLIENTS
    horizon_mega_ms = 1.05 * MEGA_CLIENTS / (0.7 * cap_qps) * 1e3
    rep = gen.run(MEGA_CLIENTS, per_client, horizon_mega_ms,
                  max_arrivals=4_000_000)
    assert rep.offered >= MEGA_CLIENTS, (
        f"million-client point offered only {rep.offered:,} arrivals")
    _report("mega-1m-clients", rep)

    # 2. bounded-queue drop policy under overload
    for mult in (QUICK_DROP_MULTS if quick else DROP_MULTS):
        offered_qps = mult * cap_qps
        drop_gen = OpenLoopLoadGen(service, batch_size=BATCH,
                                   window_ms=WINDOW_MS,
                                   max_queue=MAX_QUEUE, seed=1)
        rep = drop_gen.run(_clients_for(offered_qps), PER_CLIENT_QPS,
                           horizon)
        _report(f"drop-x{mult:g}", rep, extra=f";max_queue={MAX_QUEUE}")
        assert rep.shed_frac > 0.0, (
            f"bounded queue at {mult}x capacity shed nothing — the drop "
            "policy is not engaging")

    # 3. traffic shapes at fixed sub-capacity load
    if not quick:
        for shape in SHAPES:
            rep = gen.run(_clients_for(0.6 * cap_qps), PER_CLIENT_QPS,
                          horizon, shape=shape)
            _report(f"shape-{shape}", rep)
    else:
        rep = gen.run(_clients_for(0.6 * cap_qps), PER_CLIENT_QPS,
                      horizon, shape="flash_crowd")
        _report("shape-flash_crowd", rep)

    # 4. rebuild-window policies: open one window, measure both modes
    rng = np.random.default_rng(7)
    w2 = scenario_weights("incident", system.graph, system.partition,
                          rng, 0.02)
    open_rebuild_window(system, w2)
    try:
        stale_rep = OpenLoopLoadGen(
            system.service(ServingPolicy(rebuild=STALE_OK)),
            batch_size=BATCH, window_ms=WINDOW_MS, seed=2,
        ).run(_clients_for(0.4 * cap_qps), PER_CLIENT_QPS, horizon / 2)
        _report("window-stale-ok", stale_rep)
        assert stale_rep.stale_frac + stale_rep.certified_frac > 0.0, (
            "rebuild window open but no stale/certified answers — the "
            "window plumbing is broken")
        wait_rep = OpenLoopLoadGen(
            system.service(ServingPolicy(rebuild=CERTIFY_OR_WAIT)),
            batch_size=BATCH, window_ms=WINDOW_MS, seed=2,
        ).run(_clients_for(0.4 * cap_qps), PER_CLIENT_QPS, horizon / 2)
        _report("window-wait", wait_rep)
        assert wait_rep.stale_frac == 0.0     # waiting never serves stale
    finally:
        close_rebuild_window(system)

    # 5. open vs closed loop at the same target load: the closed fleet
    # waits for each answer, so under overload it self-throttles —
    # offered load collapses to capacity and the p99 stays flat, hiding
    # the queue the open-loop run exposes (the closed-loop fallacy the
    # harness exists to avoid; both runs use the same deterministic
    # service model so the comparison is noise-free)
    override = (5.0, 0.5)           # deliberately slow: overload regime
    clients, qps = 2_000, 1.0
    open_rep = OpenLoopLoadGen(
        system.service(ServingPolicy(rebuild=STALE_OK)),
        batch_size=64, window_ms=WINDOW_MS,
        service_ms_override=override, seed=4,
    ).run(clients, qps, horizon, max_arrivals=3_000)
    closed_rep = OpenLoopLoadGen(
        system.service(ServingPolicy(rebuild=STALE_OK)),
        batch_size=64, window_ms=WINDOW_MS,
        service_ms_override=override, closed_loop=32, seed=4,
    ).run(clients, qps, horizon)
    _report("loop-open", open_rep)
    _report("loop-closed", closed_rep, extra=";closed_loop=32")
    emit("load/loop-p99-ratio", open_rep.p99_ms / max(1e-9,
                                                      closed_rep.p99_ms),
         unit="info",
         derived=f"open_p99={open_rep.p99_ms:.1f}ms"
                 f";closed_p99={closed_rep.p99_ms:.1f}ms"
                 f";open_offered={open_rep.offered:,}"
                 f";closed_offered={closed_rep.offered:,}")
    assert closed_rep.offered < open_rep.offered, (
        "closed loop did not self-throttle below the open-loop stream")
    assert open_rep.p99_ms > closed_rep.p99_ms, (
        "open loop shows no queue the closed loop hides — the "
        "comparison mode is not measuring what it claims")


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="reduced sweep for CI smoke (keeps the "
                         "million-client point)")
    run(quick=ap.parse_args().quick)
