#!/usr/bin/env python3
"""Smoke run of the distance system's main path on a TPU.

Default (one chip): generate a road-like synthetic continent, deploy it
(``EdgeSystem.deploy(builder="jax")``), serve batches of random pairs
through ``DistanceService.submit``, apply one incremental traffic update,
and serve again. Every answer set is checked against the scalar
reference path (``query_loop``), bidirectional Dijkstra, and the uint16
tables (bit for bit against float32).

``--chips 4``: the mesh-sharded serving path only. Both sharded layouts
(B replicated, B row-sharded), on float32 and uint16 tables, are checked
bit for bit against the replicated engine on one deployment, and
per-device resident bytes are printed.

The script refuses to run anywhere but on a TPU: a CPU fallback would run
interpret-mode or XLA-reference code and prove nothing. Any failed check
raises, so the exit code is non-zero and no result line is printed. The
last line of a passing run is one JSON object naming the device.

    python chip_smoke.py              # one chip, ~10 min deploy
    python chip_smoke.py --chips 4    # sharded layouts on a 2x2 v5e host

Timings printed on the way are set-up and smoke numbers, not benchmark
results.
"""
from __future__ import annotations

import argparse
import itertools
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(REPO, "src"))
# libtpu would otherwise write its logs to a fixed directory under /tmp
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import bidirectional_dijkstra  # noqa: E402
from repro.edge import (BatchedQueryEngine, EdgeSystem,  # noqa: E402
                        ShardedBatchedEngine)
from repro.edge.engine import _engine_fn, _engine_fn_quantized  # noqa: E402
from repro.ingest import synthetic_continent  # noqa: E402
from repro.serve import ServingPolicy  # noqa: E402
from repro.update import scenario_weights  # noqa: E402

BATCH = 4096
NUM_BATCHES = 4
LOOP_SAMPLE = 256
DIJKSTRA_PAIRS = 8
DISTRICT = (16, 16)         # synthetic_continent's own district shape
# districts per side: the host index build takes about 2 s per district
# on a one-chip v5e host, so 17x17 deploys in about 10 minutes
GRID_ONE_CHIP = (17, 17)
# q = 238 borders: B is two 128-lane tiles wide on every device
GRID_FOUR_CHIPS = (6, 6)
SEED = 0
INCIDENT_INTENSITY = 0.0005


def log(msg: str) -> None:
    print(msg, flush=True)


def require_tpu(chips: int) -> list:
    devices = jax.devices()
    d0 = devices[0]
    if d0.platform != "tpu":
        sys.exit(f"chip_smoke: no TPU found (platform {d0.platform!r}); "
                 "refusing to run the kernels in a CPU fallback")
    log(f"device: platform={d0.platform} kind={d0.device_kind} "
        f"count={len(devices)}")
    if len(devices) != chips:
        sys.exit(f"chip_smoke: --chips {chips} needs exactly {chips} "
                 f"devices, found {len(devices)}")
    return devices


def use_compile_cache() -> None:
    """JAX reads JAX_COMPILATION_CACHE_DIR itself; only without it is the
    cache pinned to one fixed directory inside the checkout."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(REPO, ".jax_cache"))


def deploy(grid: tuple[int, int]) -> EdgeSystem:
    t0 = time.perf_counter()
    csr, part = synthetic_continent(grid=grid, district=DISTRICT, seed=SEED)
    g = csr.to_graph()
    log(f"deployment: grid={grid[0]}x{grid[1]} district="
        f"{DISTRICT[0]}x{DISTRICT[1]} n={g.num_vertices} m={g.num_edges} "
        f"districts={part.num_districts} "
        f"generate_s={time.perf_counter() - t0:.3f}")
    t0 = time.perf_counter()
    system = EdgeSystem.deploy(g, part, builder="jax")
    total = time.perf_counter() - t0
    center_s = system.center.last_build_seconds
    kmax = max(len(srv.augmented.vertices) for srv in system.servers)
    log(f"deploy: q={system.center.border_labels.num_borders} kmax={kmax} "
        f"deploy_s={total:.3f} center_build_s={center_s:.3f} "
        f"server_bootstrap_s={total - center_s:.3f}")
    return system


def make_batches(system: EdgeSystem, rng: np.random.Generator
                 ) -> list[tuple[np.ndarray, np.ndarray]]:
    """Random pairs; half of each batch has t drawn from s's district so
    both the district rows (rules 1/2) and B (rule 3) are served."""
    assignment = system.partition.assignment
    n = len(assignment)
    order = np.argsort(assignment, kind="stable")
    counts = np.bincount(assignment, minlength=system.partition.num_districts)
    starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
    batches = []
    for _ in range(NUM_BATCHES):
        ss = rng.integers(0, n, size=BATCH)
        ts = rng.integers(0, n, size=BATCH)
        half = BATCH // 2
        d = assignment[ss[:half]]
        ts[:half] = order[starts[d] + (rng.random(half) * counts[d])
                          .astype(np.int64)]
        batches.append((ss, ts))
    return batches


def check_kernel_step(engine, ss: np.ndarray, ts: np.ndarray) -> None:
    """The compiled steady-state step at the served shapes must hold the
    Pallas join (a Mosaic ``tpu_custom_call``)."""
    assert engine.use_pallas, "engine chose the XLA reference join"
    if isinstance(engine, ShardedBatchedEngine):
        owner, rs, rt = engine.row_ids(ss, ts)
        lowered = engine._fn.lower(engine._table, engine._btable,
                                   owner, rs, rt)
    else:
        rs, rt = engine.row_ids(ss, ts)
        if engine.quant is None:
            lowered = _engine_fn.lower(engine._table, rs, rt,
                                       use_pallas=True)
        else:
            sent, scale = engine.quant.key()
            lowered = _engine_fn_quantized.lower(
                engine._table, rs, rt, use_pallas=True, sentinel=sent,
                scale=scale)
    assert "tpu_custom_call" in lowered.compile().as_text(), \
        "compiled serving step holds no Pallas kernel"


def serve(system: EdgeSystem, policy: ServingPolicy, batches, label: str,
          engine_type: type) -> tuple[list[np.ndarray], object]:
    """Submit every batch through the front door; returns the answers
    and the engine that served them."""
    svc = system.service(policy)
    ss, ts = batches[0]
    t0 = time.perf_counter()
    plan = svc.plan(ss, ts)
    build_s = time.perf_counter() - t0
    engine = plan.plane
    assert type(engine) is engine_type, \
        f"{label}: planned {type(engine).__name__}, " \
        f"expected {engine_type.__name__}"
    t0 = time.perf_counter()
    out = [plan.execute().distances]
    first_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    for ss, ts in batches[1:]:
        out.append(svc.submit(ss, ts).distances)
    warm_s = (time.perf_counter() - t0) / (len(batches) - 1)
    check_kernel_step(engine, *batches[0])
    dtype = "float32" if engine.quant is None else str(engine.quant.dtype)
    log(f"serve[{label}]: engine={type(engine).__name__} tables={dtype} "
        f"use_pallas={engine.use_pallas} tpu_custom_call=yes "
        f"size_bytes={engine.size_bytes()} batches={len(batches)}x{BATCH} "
        f"engine_build_s={build_s:.3f} first_call_s={first_s:.3f} "
        f"warm_batch_s={warm_s:.4f}")
    for d in out:
        assert d.shape == (BATCH,) and np.isfinite(d).all(), \
            f"{label}: non-finite or misshapen answers"
    return out, engine


def check_answers(system: EdgeSystem, batches, f32, u16,
                  label: str) -> None:
    for i, (a, b) in enumerate(zip(f32, u16)):
        np.testing.assert_array_equal(a, b, err_msg=f"{label}: uint16 vs "
                                      f"float32, batch {i}")
    ss, ts = batches[0]
    loop = system.query_loop(ss[:LOOP_SAMPLE], ts[:LOOP_SAMPLE])
    np.testing.assert_array_equal(f32[0][:LOOP_SAMPLE], loop,
                                  err_msg=f"{label}: engine vs query_loop")
    # pairs from both halves: same-district and uniform
    half = BATCH // 2
    idx = list(range(DIJKSTRA_PAIRS // 2)) + \
        list(range(half, half + DIJKSTRA_PAIRS // 2))
    t0 = time.perf_counter()
    for i in idx:
        ref = bidirectional_dijkstra(system.graph, int(ss[i]), int(ts[i]))
        assert f32[0][i] == np.float32(ref), \
            f"{label}: pair ({ss[i]}, {ts[i]}) served {f32[0][i]}, " \
            f"Dijkstra {ref}"
    log(f"check[{label}]: uint16==float32 on {len(f32)}x{BATCH}, "
        f"query_loop parity on {LOOP_SAMPLE}, Dijkstra parity on "
        f"{len(idx)} pairs ({time.perf_counter() - t0:.3f}s)")


def one_chip() -> None:
    system = deploy(GRID_ONE_CHIP)
    rng = np.random.default_rng(SEED)
    batches = make_batches(system, rng)
    f32 = ServingPolicy(label_dtype="float32")
    u16 = ServingPolicy(label_dtype="uint16")

    before, _ = serve(system, f32, batches, "float32", BatchedQueryEngine)
    before16, _ = serve(system, u16, batches, "uint16", BatchedQueryEngine)
    check_answers(system, batches, before, before16, "before update")

    w = scenario_weights("incident", system.graph, system.partition, rng,
                         intensity=INCIDENT_INTENSITY)
    t0 = time.perf_counter()
    rep = system.apply_traffic_update(w, incremental=True)
    log(f"update: incident intensity={INCIDENT_INTENSITY} "
        f"update_s={time.perf_counter() - t0:.3f} "
        f"center_repair_s={rep['bl_rebuild_s']:.3f} "
        f"incremental={rep['incremental']} "
        f"dirty_districts={len(rep['dirty_districts'])} "
        f"reinstalled={len(rep['shortcut_install_s'])} "
        f"version={system.center.version}")

    after, _ = serve(system, f32, batches, "float32 after update",
                     BatchedQueryEngine)
    after16, _ = serve(system, u16, batches, "uint16 after update",
                       BatchedQueryEngine)
    check_answers(system, batches, after, after16, "after update")
    moved = sum(int((a != b).sum()) for a, b in zip(before, after))
    log(f"update moved {moved} of {NUM_BATCHES * BATCH} answers")
    stats = jax.devices()[0].memory_stats() or {}
    log(f"device memory: peak_bytes_in_use="
        f"{stats.get('peak_bytes_in_use', 'not reported')}")


def four_chips() -> None:
    system = deploy(GRID_FOUR_CHIPS)
    batches = make_batches(system, np.random.default_rng(SEED))
    ref, _ = serve(system, ServingPolicy(engine="replicated",
                                         label_dtype="float32"),
                   batches, "replicated", BatchedQueryEngine)
    devices = {d.id for d in jax.devices()}
    # both table dtypes: the uint16 layout assembles B rows with its own
    # collective
    for dtype, shard_border in itertools.product(("float32", "uint16"),
                                                 (False, True)):
        label = f"sharded {dtype} shard_border={shard_border}"
        policy = ServingPolicy(engine="sharded", shard_border=shard_border,
                               label_dtype=dtype)
        got, engine = serve(system, policy, batches, label,
                            ShardedBatchedEngine)
        for i, (a, b) in enumerate(zip(ref, got)):
            np.testing.assert_array_equal(
                a, b, err_msg=f"{label} vs replicated, batch {i}")
        mesh_ids = {d.id for d in engine.mesh.devices.flat}
        assert mesh_ids == devices, \
            f"{label}: mesh spans {sorted(mesh_ids)}, devices " \
            f"{sorted(devices)}"
        held: dict[int, int] = {}
        for arr in (engine._table, engine._btable):
            for shard in arr.addressable_shards:
                held[shard.device.id] = (held.get(shard.device.id, 0)
                                         + shard.data.nbytes)
        log(f"{label}: bit-for-bit == replicated on "
            f"{len(batches)}x{BATCH}; mesh devices={sorted(mesh_ids)}; "
            f"resident bytes per device: accounted={engine.size_bytes()} "
            f"held={[held[d] for d in sorted(held)]}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the sharded serving path")
    args = ap.parse_args()
    devices = require_tpu(args.chips)
    use_compile_cache()
    t0 = time.perf_counter()
    if args.chips == 4:
        four_chips()
    else:
        one_chip()
    log(f"smoke wall_s={time.perf_counter() - t0:.3f}")
    d0 = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
