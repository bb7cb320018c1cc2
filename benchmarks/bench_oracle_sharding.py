"""§Perf (paper technique): index placement on the device mesh.

Two experiments on virtual host meshes:

1. Border-table placement — replicating B (the computing center) costs
   n·q·4 bytes per device but answers rule-3 queries with zero
   collectives; row-sharding B cuts memory by the device count but every
   cross-district query fetches two q-wide rows across shards. Compiles
   both layouts on an 8-device mesh and reports per-device index bytes +
   collective bytes per 4096-query batch from the optimized HLO.

2. ShardedBatchedEngine sweep — batch size × device count for the
   serving engine that shards the combined district tables over the
   ``edge`` axis, in BOTH border-table placements: B replicated at its
   natural width q (``engine-E{E}-b{b}`` rows) and B row-sharded too
   (``engine-border-E{E}-b{b}`` rows). Reports µs/query and per-device
   resident bytes: the district block shrinks ≈ 1/E, and the B-sharded
   layout's resident fraction ≈ district_frac/E + (n/E)·q — strictly
   below the replicated-B layout at E ≥ 2. Each device count runs in
   its own subprocess because XLA_FLAGS must be set before jax
   initializes.

``--quick`` runs a reduced sweep (E ∈ {1, 2}, one batch size) — the CI
docs job invokes it so the sweep can't silently rot.
"""
from __future__ import annotations

import argparse

from .common import emit, engine_sweep_code, run_json_subprocess

ENGINE_DEVICE_COUNTS = (1, 2, 4, 8)
ENGINE_BATCH_SIZES = (256, 1024, 4096)
QUICK_DEVICE_COUNTS = (1, 2)
QUICK_BATCH_SIZES = (256,)
ENGINE_SETUP = ("g = grid_road_network(24, 24, seed=3); "
                "part = bfs_grow_partition(g, 8, seed=0)")

CODE = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import re, json
import jax, jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from repro.core import DistanceOracle, bfs_grow_partition, grid_road_network

g = grid_road_network(24, 24, seed=3)
part = bfs_grow_partition(g, 8, seed=0)
oracle = DistanceOracle.build(g, part)
bt = oracle.border_labels.table.astype(np.float32)
n, q = bt.shape
pad = (-n) % 8
if pad:
    bt = np.pad(bt, ((0, pad), (0, 0)), constant_values=np.inf)
mesh = Mesh(np.array(jax.devices()).reshape(8), ("edge",))
Q = 4096
rng = np.random.default_rng(0)
ss = jnp.asarray(rng.integers(0, n, size=Q))
ts = jnp.asarray(rng.integers(0, n, size=Q))

def query(table, s, t):
    return jnp.min(table[s] + table[t], axis=1)

out = {}
for name, spec in (("replicated", P()), ("row-sharded", P("edge"))):
    sh = NamedSharding(mesh, spec)
    rep = NamedSharding(mesh, P())
    j = jax.jit(query, in_shardings=(sh, rep, rep), out_shardings=rep)
    comp = j.lower(jax.ShapeDtypeStruct(bt.shape, jnp.float32),
                   jax.ShapeDtypeStruct(ss.shape, ss.dtype),
                   jax.ShapeDtypeStruct(ts.shape, ts.dtype)).compile()
    hlo = comp.as_text()
    coll = 0
    for line in hlo.splitlines():
        m = re.search(r"\b(all-gather|all-reduce|reduce-scatter|"
                      r"all-to-all|collective-permute)\b", line)
        if m:
            sm = re.findall(r"(f32|s32|u32|pred)\[([0-9,]*)\]",
                            line.split("=", 1)[0])
            for dt, dims in sm:
                nelem = 1
                for d in dims.split(","):
                    if d:
                        nelem *= int(d)
                coll += nelem * 4
    mem = comp.memory_analysis()
    out[name] = {"arg_mb": mem.argument_size_in_bytes / 1e6,
                 "coll_mb": coll / 1e6}
print(json.dumps({"n": int(n), "q": int(q), **out}))
"""


def run(quick: bool = False) -> None:
    r = run_json_subprocess(CODE)
    for name in ("replicated", "row-sharded"):
        emit(f"oracle-sharding/{name}",
             r[name]["coll_mb"] * 1e3,  # KB collectives per 4k queries
             f"arg_mb_per_dev={r[name]['arg_mb']:.2f};n={r['n']};q={r['q']}"
             f";col2=coll_kb_per_4k_queries", unit="bytes",
             config={"backend": r["backend"]})
    run_engine_sweep(quick=quick)


def run_engine_sweep(quick: bool = False) -> None:
    """ShardedBatchedEngine: batch × device-count sweep + memory scaling
    for both border-table placements (B replicated / B row-sharded)."""
    device_counts = QUICK_DEVICE_COUNTS if quick else ENGINE_DEVICE_COUNTS
    batches = QUICK_BATCH_SIZES if quick else ENGINE_BATCH_SIZES
    for ndev in device_counts:
        r = run_json_subprocess(
            engine_sweep_code(ENGINE_SETUP, ndev, batches))
        # district tables shrink 1/E (vs the replicated DISTRICT rows —
        # exactly 1.0 at E=1); resident adds each layout's share of B and
        # is compared against the full combined replicated table
        dfrac = r["per_device_table_bytes"] / r["replicated_district_bytes"]
        rfrac = r["per_device_resident_bytes"] / r["replicated_table_bytes"]
        bfrac = r["border_resident_bytes"] / r["replicated_table_bytes"]
        if ndev >= 2 and r["q"]:
            # acceptance: fully-sharded resident strictly below the
            # replicated-B sharded layout once there is more than 1 device
            assert r["border_resident_bytes"] < r["per_device_resident_bytes"]
        for b, sec in r["sweep"].items():
            emit(f"oracle-sharding/engine-E{ndev}-b{b}",
                 sec / int(b) * 1e6,
                 f"qps={int(b) / sec:,.0f}"
                 f";table_bytes_per_dev={r['per_device_table_bytes']}"
                 f";district_frac={dfrac:.3f};resident_frac={rfrac:.3f}",
                 config={"backend": r["backend"]})
        for b, sec in r["sweep_border"].items():
            emit(f"oracle-sharding/engine-border-E{ndev}-b{b}",
                 sec / int(b) * 1e6,
                 f"qps={int(b) / sec:,.0f}"
                 f";border_bytes_per_dev={r['border_table_bytes_per_device']}"
                 f";district_frac={dfrac:.3f}"
                 f";border_resident_frac={bfrac:.3f}"
                 f";n={r['n']};q={r['q']}",
                 config={"backend": r["backend"]})


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="reduced sweep for CI smoke (E in {1,2}, one "
                         "batch size)")
    run(quick=ap.parse_args().quick)
