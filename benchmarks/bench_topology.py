"""Dynamic-topology sweep: structural repair vs full rebuild, and
district migration under live load.

Two sections on the 24×24 / 8-district grid of ``bench_update``:

1. **Closure-storm repair** — ``ingest.closure_storm`` epochs (edges
   close and reopen; intra-biased so the Definition-4 border sets stay
   stable and the *scoped* structural path is what's measured).  Every
   epoch first asserts the structural repair is **bit-for-bit equal**
   to a from-scratch build on the new topology, then times both paths
   (best-of-N, jit-warm, fresh builder per full build) and asserts the
   repair strictly beats the rebuild for every sub-10%-dirty epoch
   whose border sets did not move.
2. **Migration under load** — a skewed query mix drives one edge host
   hot; ``RebalancePlanner`` plans the moves, the §5 simulator executes
   them mid-run on the live clock, and the run asserts **zero
   non-exact answers outside the declared migration window** (the
   ``dual`` discipline serves exactly throughout; ``handoff`` flags
   only inside the window).  The real ``EdgeSystem.migrate`` swap +
   engine re-pack is timed as the install cost.

``--quick`` runs a reduced sweep — the CI docs job invokes it so the
parity + exactness assertions can't silently rot.
"""
from __future__ import annotations

import argparse
import time

import numpy as np

from .common import emit

STORM_INTENSITIES = (0.01, 0.03)
QUICK_INTENSITIES = (0.02,)
NUM_EPOCHS = 3
NUM_HOSTS = 4


def _storm_section(quick: bool) -> None:
    from repro.core import bfs_grow_partition, grid_road_network
    from repro.ingest import closure_storm
    from repro.topo import classify_structural
    from repro.update import IncrementalBuilder

    g = grid_road_network(24, 24, seed=3)
    part = bfs_grow_partition(g, 8, seed=0)
    reps = 1 if quick else 3
    epochs = 2 if quick else NUM_EPOCHS
    for intensity in (QUICK_INTENSITIES if quick else STORM_INTENSITIES):
        builder = IncrementalBuilder()
        builder.build_full(g, part)
        g_prev = g
        for k, (g_new, info) in enumerate(closure_storm(
                g, part, num_epochs=epochs, intensity=intensity,
                reopen_frac=0.5, intra_bias=1.0, seed=17)):
            delta = classify_structural(g_prev, part, g_new)
            # the repair path consumes the builder's cached state AND
            # its CSR identity tokens — snapshot both for re-timing
            st_prev = builder.state
            ip_prev, ix_prev = builder._indptr, builder._indices

            # parity first (and jit warm-up for both paths)
            full_labels = IncrementalBuilder().build_full(g_new, part)
            labels, rep = builder.apply_structural(g_new, part, delta)
            np.testing.assert_array_equal(labels.table, full_labels.table)

            best_full = best_inc = float("inf")
            for _ in range(reps):
                t0 = time.perf_counter()
                IncrementalBuilder().build_full(g_new, part)
                best_full = min(best_full, time.perf_counter() - t0)
                builder.state = st_prev
                builder._indptr, builder._indices = ip_prev, ix_prev
                t0 = time.perf_counter()
                builder.apply_structural(g_new, part, delta)
                best_inc = min(best_inc, time.perf_counter() - t0)

            scoped = rep["incremental"] and not rep["border_changed"]
            if delta.frac_dirty < 0.10 and scoped:
                # acceptance: the scoped structural repair strictly
                # beats a full rebuild for every sub-10%-dirty closure
                # epoch that leaves the border sets alone
                assert best_inc < best_full, (
                    f"storm@{intensity} epoch {k}: structural repair "
                    f"{best_inc * 1e3:.1f} ms not below full "
                    f"{best_full * 1e3:.1f} ms "
                    f"(frac_dirty={delta.frac_dirty:.3f})")
            emit(f"topology/storm-i{intensity:g}-e{k}", best_inc * 1e3,
                 f"full_ms={best_full * 1e3:.1f}"
                 f";speedup={best_full / best_inc:.2f}"
                 f";closed={info['num_closed']}"
                 f";reopened={info['num_reopened']}"
                 f";frac_dirty={delta.frac_dirty:.3f}"
                 f";dirty_districts={len(delta.dirty_districts)}"
                 f";border_changed={rep['border_changed']}"
                 f";col1=structural_ms", unit="ms")
            g_prev = g_new


def _migration_section(quick: bool) -> None:
    from repro.core import bfs_grow_partition, grid_road_network
    from repro.edge import EdgeSystem, Topology
    from repro.edge.simulator import (UpdateSchedule, make_trace,
                                      migrations_from_plan, simulate_edge)
    from repro.serve import ServingPolicy
    from repro.topo import EdgePlacement, RebalancePlanner

    g = grid_road_network(24, 24, seed=3)
    part = bfs_grow_partition(g, 8, seed=0)
    system = EdgeSystem.deploy(g, part)
    m = part.num_districts

    # skewed load: the districts of host 0 take most of the traffic
    placement = EdgePlacement.blocked(m, NUM_HOSTS)
    planner = RebalancePlanner.for_system(system, NUM_HOSTS,
                                          max_moves=2)
    load = np.ones(m)
    load[placement.districts_of(0)] = 40.0
    planner.observe_load(load)
    t0 = time.perf_counter()
    plan = planner.plan()
    plan_s = time.perf_counter() - t0
    assert plan is not None and plan.imbalance_after < plan.imbalance_before
    emit("topology/rebalance-plan", plan_s * 1e3,
         f"moves={len(plan.moves)}"
         f";imbalance={plan.imbalance_before:.2f}"
         f"->{plan.imbalance_after:.2f}", unit="ms")

    # the real system swap: placement install + engine re-pack (the
    # pack memcpys cached dense tables — only coordinates move)
    t0 = time.perf_counter()
    system.migrate(plan)
    engine = system._current_engine(ServingPolicy(engine="sharded"))
    _ = engine.query(np.zeros(8, np.int64), np.zeros(8, np.int64))
    swap_s = time.perf_counter() - t0
    emit("topology/migrate-swap", swap_s * 1e3,
         f"placement_version={plan.placement.version}"
         f";moved={len(plan.moves)}", unit="ms")

    # migration under live load on the simulated clock: biased trace,
    # swap mid-run, exactness asserted outside the declared window
    nq = 4_000 if quick else 20_000
    trace = make_trace(g, nq, 4_000.0, seed=5)
    sched = UpdateSchedule(1e9, 0.0, 0.0, 0.0)      # no rebuild windows
    migs = migrations_from_plan(plan, t_ms=2_000.0, copy_ms=200.0)
    for mode in ("dual", "handoff"):
        res = simulate_edge(trace, Topology(m), sched, part.assignment,
                            lambda s, t: True, m,
                            policy=ServingPolicy(migration=mode),
                            placement=placement, migrations=migs)
        outside = res.nonexact_mask & ~res.migration_window_mask
        assert not outside.any(), (
            f"{mode}: {int(outside.sum())} non-exact answers OUTSIDE "
            "the declared migration window")
        if mode == "dual":
            assert not res.nonexact_mask.any(), (
                "dual-serve migration must stay exact everywhere")
        emit(f"topology/migration-{mode}-p99", res.p99_ms,
             f"p50={res.p50_ms:.2f}ms"
             f";window_frac={res.migration_window_mask.mean():.4f}"
             f";migration_stale={res.migration_stale_frac:.4f}",
             unit="ms")


def run(quick: bool = False) -> None:
    _storm_section(quick)
    _migration_section(quick)


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="reduced sweep for CI smoke")
    run(quick=ap.parse_args().quick)
