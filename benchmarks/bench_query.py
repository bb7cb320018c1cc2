"""Fig. 5 analogue: per-query response time + batched engine throughput.

Hub-labeling methods (ours = BL + district L_i⁺) answer in microseconds;
online bidirectional Dijkstra is the millisecond-level baseline family.
Batched joins (the TPU serving layout) are reported separately — that's
the number the edge deployment actually serves at: the second section
sweeps the ``DistanceService`` engine path (the single-dispatch
combined-table engine) over batch sizes 64–4096 against the per-query
Python loop, the third section measures the service FRONT DOOR itself —
``DistanceService.submit`` (routing + plan + metadata wrap) versus the
raw engine-plane call, asserting the dispatch overhead stays under 10 %
at batch ≥ 1024 — and the last section re-runs the sweep through the
mesh-sharded ``ShardedBatchedEngine`` on 8 virtual host devices
(subprocess, so the main process keeps its single-device backend),
reporting the per-device district-table footprint next to the
replicated engine's.
"""
from __future__ import annotations

import time

import numpy as np

from repro.core import (DistanceOracle, bidirectional_dijkstra,
                        grid_partition, grid_road_network, pll)
from repro.edge import EdgeSystem

from .common import emit, engine_sweep_code, run_json_subprocess, timeit

NUM_QUERIES = 10_000
BIDIJ_QUERIES = 50
ENGINE_BATCH_SIZES = (64, 256, 1024, 4096)
ENGINE_LOOP_QUERIES = 1024
FRONT_DOOR_BATCH_SIZES = (256, 1024, 4096)
FRONT_DOOR_MAX_OVERHEAD = 0.10      # at batch >= 1024
SHARDED_DEVICES = 8
SHARDED_BATCH_SIZES = (256, 1024, 4096)
SHARDED_SETUP = ("g = grid_road_network(50, 50, seed=7); "
                 "part = grid_partition(g, 50, 50, 3, 4)")


def run(quick: bool = False) -> None:
    g = grid_road_network(50, 50, seed=7)
    part = grid_partition(g, 50, 50, 3, 4)
    oracle = DistanceOracle.build(g, part)
    full = pll(g)
    rng = np.random.default_rng(1)
    num_queries = NUM_QUERIES // 5 if quick else NUM_QUERIES
    bidij_queries = 10 if quick else BIDIJ_QUERIES
    ss = rng.integers(0, g.num_vertices, size=num_queries)
    ts = rng.integers(0, g.num_vertices, size=num_queries)

    _, sec = timeit(lambda: oracle.query_many(ss, ts), repeats=3)
    emit("query/ours-BL-batched", sec / num_queries * 1e6,
         f"n={g.num_vertices};q={num_queries}")

    sel = rng.integers(0, num_queries, size=100 if quick else 500)
    _, sec = timeit(lambda: [oracle.query(int(ss[i]), int(ts[i]))
                             for i in sel], repeats=2)
    emit("query/ours-BL-single", sec / len(sel) * 1e6, "per-call python")

    _, sec = timeit(lambda: full.query_many(ss, ts), repeats=3)
    emit("query/PLL-batched", sec / num_queries * 1e6,
         f"labels_mb={full.size_bytes()/1e6:.2f}")

    _, sec = timeit(lambda: [bidirectional_dijkstra(g, int(ss[i]),
                                                    int(ts[i]))
                             for i in range(bidij_queries)], repeats=1,
                    warmup=0)
    emit("query/BiDijkstra", sec / bidij_queries * 1e6,
         "online-search baseline")

    system = run_engine(g, part, rng)
    run_front_door(g, part, rng, system=system)
    if not quick:       # the oracle_sharding --quick sweep covers the
        run_sharded()   # subprocess engine path at E in {1, 2}


def run_engine(g=None, part=None, rng=None):
    """Batched edge-serving engine: queries/sec at batch sizes 64–4096
    versus the single-query Python path through the same EdgeSystem.
    Returns the deployed system so later sections skip the deploy."""
    if g is None:
        g = grid_road_network(50, 50, seed=7)
        part = grid_partition(g, 50, 50, 3, 4)
        rng = np.random.default_rng(1)
    system = EdgeSystem.deploy(g, part)
    service = system.service()

    ss = rng.integers(0, g.num_vertices, size=ENGINE_LOOP_QUERIES)
    ts = rng.integers(0, g.num_vertices, size=ENGINE_LOOP_QUERIES)
    _, loop_sec = timeit(lambda: system.query_loop(ss, ts), repeats=2)
    loop_us = loop_sec / ENGINE_LOOP_QUERIES * 1e6
    emit("engine/single-query-loop", loop_us, "per-call python path")

    speedup_1024 = None
    for b in ENGINE_BATCH_SIZES:
        sb = rng.integers(0, g.num_vertices, size=b)
        tb = rng.integers(0, g.num_vertices, size=b)
        _, sec = timeit(lambda: service.distances(sb, tb), repeats=5)
        qps = b / sec
        if b == 1024:
            speedup_1024 = loop_sec / ENGINE_LOOP_QUERIES / (sec / b)
        emit(f"engine/batched-{b}", sec / b * 1e6, f"qps={qps:,.0f}")
    if speedup_1024 is not None:    # 1024 could be dropped from the sweep
        emit("engine/speedup-vs-loop-1024", speedup_1024,
             "x faster per query at batch 1024", unit="speedup_x")
    return system


def run_front_door(g=None, part=None, rng=None, system=None) -> None:
    """DistanceService dispatch overhead: the full front door
    (``submit`` = §4.2 routing pass + plan + plane dispatch + metadata
    wrap + counter aggregation) versus the raw engine plane
    (``QueryPlane.execute`` on pre-built row ids is what ``submit``
    wraps).  The request-plane tax must stay under
    FRONT_DOOR_MAX_OVERHEAD at batch >= 1024 on CPU."""
    if g is None:
        g = grid_road_network(50, 50, seed=7)
        part = grid_partition(g, 50, 50, 3, 4)
        rng = np.random.default_rng(1)
    if system is None:
        system = EdgeSystem.deploy(g, part)
    service = system.service()
    for b in FRONT_DOOR_BATCH_SIZES:
        sb = rng.integers(0, g.num_vertices, size=b)
        tb = rng.integers(0, g.num_vertices, size=b)
        service.submit(sb, tb)              # warm the engine + jit cache
        # the raw engine call IS the plane dispatch inside submit, and
        # ResultBatch.latency_s records it per call — measuring both
        # sides of the SAME invocation factors out the large run-to-run
        # jitter of the jitted join itself
        overheads, totals, planes = [], [], []
        for _ in range(9):
            t0 = time.perf_counter()
            batch = service.submit(sb, tb)
            total = time.perf_counter() - t0
            totals.append(total)
            planes.append(batch.latency_s)
            overheads.append((total - batch.latency_s) / batch.latency_s)
        overhead = float(np.median(overheads))
        emit(f"service/front-door-{b}", min(totals) / b * 1e6,
             f"plane_dispatch={min(planes) / b * 1e6:.3f}us"
             f";overhead={overhead * 100:.1f}%")
        if b >= 1024:
            assert overhead < FRONT_DOOR_MAX_OVERHEAD, (
                f"DistanceService dispatch overhead {overhead:.1%} at "
                f"batch {b} exceeds {FRONT_DOOR_MAX_OVERHEAD:.0%}")


def run_sharded() -> None:
    """Mesh-sharded engine sweep on 8 virtual host devices (subprocess:
    XLA_FLAGS must be set before jax initializes), in both border-table
    placements. Answers are asserted identical to the replicated engine
    before timing."""
    r = run_json_subprocess(engine_sweep_code(
        SHARDED_SETUP, SHARDED_DEVICES, SHARDED_BATCH_SIZES))
    dfrac = r["per_device_table_bytes"] / r["replicated_district_bytes"]
    rfrac = r["per_device_resident_bytes"] / r["replicated_table_bytes"]
    bfrac = r["border_resident_bytes"] / r["replicated_table_bytes"]
    for b, sec in r["sweep"].items():
        emit(f"engine/sharded-{b}", sec / int(b) * 1e6,
             f"qps={int(b) / sec:,.0f};devices={r['devices']}",
             config={"backend": r["backend"]})
    for b, sec in r["sweep_border"].items():
        emit(f"engine/border-sharded-{b}", sec / int(b) * 1e6,
             f"qps={int(b) / sec:,.0f};devices={r['devices']}",
             config={"backend": r["backend"]})
    emit("engine/sharded-table-bytes-per-device",
         r["per_device_table_bytes"],
         f"replicated={r['replicated_table_bytes']}"
         f";district_frac={dfrac:.3f};resident_frac={rfrac:.3f}",
         unit="bytes",
         config={"backend": r["backend"]})
    emit("engine/border-sharded-resident-bytes-per-device",
         r["border_resident_bytes"],
         f"replicated={r['replicated_table_bytes']}"
         f";border_bytes_per_dev={r['border_table_bytes_per_device']}"
         f";border_resident_frac={bfrac:.3f};n={r['n']};q={r['q']}",
         unit="bytes",
         config={"backend": r["backend"]})


if __name__ == "__main__":
    run()
