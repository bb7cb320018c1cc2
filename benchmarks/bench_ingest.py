"""Continent-scale ingest + quantized label storage (repro.ingest).

Everything runs at the 10^5-vertex synthetic-continent point (a 4x4
mosaic of 80x80 grid districts, n = 102 400, no downloads):

1. ``parse-gr`` — the continent's arcs are written to a temp DIMACS
   ``.gr`` file and streamed back through ``ingest.dimacs.iter_gr``
   (parse throughput in Marcs/s);
2. ``csr-build`` — ``CSRBuilder`` dedupe/sort/finalize from raw arc
   chunks;
3. ``index-build`` — ``build_border_labels_hierarchical`` on the
   ingested graph (the end of the ingest -> CSR -> build path);
4. resident bytes — the border table B stored as float32 vs uint16
   ``core.quantize`` codes, plus their ratio (unit ``bytes_ratio`` so
   ``compare.py``'s +-2% bytes gate rides every row);
5. ``e2e-query`` — quantized rule-3 joins on the 10^5 table, asserted
   bit-for-bit against the float32 join and spot-checked against
   bidirectional Dijkstra ground truth (the query end of the path).

A subprocess pinned to an 8-device host mesh packs the full serving
engine (district block + B) at a smaller continent point in both
dtypes, asserts answer parity, and asserts per-device resident bytes
<= QUANT_BYTES_CEILING x float32 at E = 8 — the acceptance bound for
the quantized layout.

``--quick`` keeps the full 10^5 end-to-end path (that it runs in CI is
itself an acceptance criterion) and drops only the extra 2.5x10^5
index-build point.
"""
from __future__ import annotations

import argparse
import os
import tempfile
import time

import numpy as np

from .common import emit, run_json_subprocess, timeit

# the 10^5-vertex continent point: 16 districts of 6 400 vertices
GRID, DISTRICT = (4, 4), (80, 80)
# full-profile extra index-build point (2.5x10^5 vertices)
GRID_FULL, DISTRICT_FULL = (5, 5), (100, 100)
SEED = 7
QUERY_BATCH = 4096
DIJKSTRA_SPOT_PAIRS = 6
# acceptance: quantized per-device resident bytes at E=8 vs float32
QUANT_BYTES_CEILING = 0.55

# 8-device engine parity + bytes: XLA_FLAGS must be set before jax
# initializes, so the mesh sweep runs in its own interpreter (same
# pattern as bench_oracle_sharding).  The continent point is smaller
# (4096 vertices) because the engine packs every district table dense.
CODE_E8 = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import numpy as np
from repro.ingest import synthetic_continent
from repro.core import (build_all_local_indexes,
                        build_border_labels_hierarchical)
from repro.core.quantize import fit_label_spec
from repro.edge.engine import ShardedBatchedEngine
from repro.edge.sharded_oracle import default_edge_mesh

csr, part = synthetic_continent(grid=(4, 4), district=(16, 16),
                                border_links=2, seed=5)
g = csr.to_graph()
bl = build_border_labels_hierarchical(g, part)
locals_ = build_all_local_indexes(g, part, bl=bl)
bt = bl.table.astype(np.float32)
mesh = default_edge_mesh(8)

spec = fit_label_spec(bt, locals_)
assert spec.lossless, "integral continent weights must fit losslessly"
f32 = ShardedBatchedEngine(bt, locals_, part.assignment, mesh=mesh)
u16 = ShardedBatchedEngine(bt, locals_, part.assignment, mesh=mesh,
                           quant=spec)

rng = np.random.default_rng(1)
ss = rng.integers(0, g.num_vertices, size=2048)
ts = rng.integers(0, g.num_vertices, size=2048)
ref = np.asarray(f32.query(ss, ts))
got = np.asarray(u16.query(ss, ts))
assert np.array_equal(ref, got), \
    "uint16 engine answers diverge from float32 at E=8"
print(json.dumps({
    "n": int(g.num_vertices), "q": int(len(bl.border_ids)),
    "f32_bytes_per_device": int(f32.size_bytes()),
    "u16_bytes_per_device": int(u16.size_bytes()),
    "parity_queries": int(len(ss)),
}))
"""


def _write_gr(csr, path: str) -> int:
    """Serialize a CSR back to DIMACS ``.gr`` (both arc directions, the
    format's native form); returns the arc count."""
    us = np.repeat(np.arange(csr.num_vertices), np.diff(csr.indptr))
    with open(path, "w") as f:
        f.write("c synthetic continent (bench_ingest)\n"
                f"p sp {csr.num_vertices} {len(us)}\n")
        np.savetxt(f, np.column_stack(
            [us + 1, csr.indices + 1, csr.weights.astype(np.int64)]),
            fmt="a %d %d %d")
    return len(us)


def _parse_and_csr(path: str, n: int):
    """Time the two ingest stages separately: streaming parse, then
    CSR dedupe/sort/finalize over the buffered chunks."""
    from repro.ingest import iter_gr
    from repro.ingest.csr import CSRBuilder
    t0 = time.perf_counter()
    chunks = [(u, v, w) for _, u, v, w in iter_gr(path)]
    parse_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    builder = CSRBuilder(n)
    for u, v, w in chunks:
        builder.add_arcs(u, v, w)
    csr = builder.finalize()
    csr_s = time.perf_counter() - t0
    return csr, parse_s, csr_s


def _e2e_query_check(g, part, bl, quick: bool) -> tuple[float, int]:
    """Rule-3 joins on the 10^5 B table: uint16 codes must reproduce
    the float32 answers bit-for-bit, and both must match Dijkstra on
    cross-district spot pairs.  Returns (best_seconds, batch)."""
    from repro.core import bidirectional_dijkstra
    from repro.core.quantize import QuantSpec
    from repro.kernels.label_join import ops as lj

    bt = bl.table.astype(np.float32)
    spec = QuantSpec.fit(bt)
    assert spec.lossless, "integral continent weights must fit losslessly"
    codes = spec.quantize(bt)

    rng = np.random.default_rng(SEED)
    n = g.num_vertices
    ss = rng.integers(0, n, size=QUERY_BATCH)
    ts = rng.integers(0, n, size=QUERY_BATCH)
    ref = lj.join_gathered(bt, ss, ts)
    sent, scale = spec.key()

    def joinq():
        return lj.join_quantized_gathered(codes, ss, ts, sentinel=sent,
                                          scale=scale)

    got, sec = timeit(joinq, repeats=1 if quick else 3, warmup=1)
    assert np.array_equal(ref, got), \
        "uint16 join answers diverge from float32 at the 1e5 point"

    cross = part.assignment[ss] != part.assignment[ts]
    spots = np.flatnonzero(cross)[:DIJKSTRA_SPOT_PAIRS]
    for i in spots:
        d = bidirectional_dijkstra(g, int(ss[i]), int(ts[i]))
        assert got[i] == np.float32(d), \
            f"query ({ss[i]},{ts[i]}): join {got[i]} != dijkstra {d}"
    return sec, len(spots)


def _index_build_point(grid, district, tag: str) -> None:
    """Extra index-build scaling point (full profile only)."""
    from repro.core import build_border_labels_hierarchical
    from repro.ingest import synthetic_continent
    csr, part = synthetic_continent(grid=grid, district=district,
                                    border_links=2, seed=SEED)
    g = csr.to_graph()
    t0 = time.perf_counter()
    bl = build_border_labels_hierarchical(g, part)
    sec = time.perf_counter() - t0
    emit(f"ingest/index-build-{tag}", sec,
         f"n={g.num_vertices};q={len(bl.border_ids)}", unit="s")


def run(quick: bool = False) -> None:
    from repro.core import build_border_labels_hierarchical
    from repro.core.quantize import QuantSpec
    from repro.ingest import synthetic_continent

    # --- ingest -> CSR -> build -> query at the 10^5 point -----------
    t0 = time.perf_counter()
    csr, part = synthetic_continent(grid=GRID, district=DISTRICT,
                                    border_links=2, seed=SEED)
    synth_s = time.perf_counter() - t0
    n, m = csr.num_vertices, csr.num_edges
    emit("ingest/synth-1e5", synth_s, f"n={n};m={m}", unit="s")

    fd, path = tempfile.mkstemp(suffix=".gr")
    os.close(fd)
    try:
        arcs = _write_gr(csr, path)
        csr2, parse_s, csr_s = _parse_and_csr(path, n)
    finally:
        os.unlink(path)
    assert csr2.num_edges == m, "round-trip through .gr changed the graph"
    emit("ingest/parse-gr-1e5", parse_s,
         f"arcs={arcs};Marcs_per_s={arcs / parse_s / 1e6:.2f}", unit="s")
    emit("ingest/csr-build-1e5", csr_s, f"arcs={arcs};edges={m}", unit="s")

    g = csr.to_graph()
    t0 = time.perf_counter()
    bl = build_border_labels_hierarchical(g, part)
    build_s = time.perf_counter() - t0
    q = len(bl.border_ids)
    emit("ingest/index-build-1e5", build_s, f"n={n};q={q}", unit="s")

    # --- resident bytes: float32 vs uint16 B table -------------------
    bt = bl.table.astype(np.float32)
    spec = QuantSpec.fit(bt)
    f32_bytes = bt.nbytes
    u16_bytes = bt.size * spec.itemsize
    emit("ingest/btable-bytes-f32", f32_bytes, f"n={n};q={q}",
         unit="bytes")
    emit("ingest/btable-bytes-u16", u16_bytes,
         f"lossless={spec.lossless};scale={spec.scale:g}", unit="bytes")
    emit("ingest/quantized-bytes-ratio", u16_bytes / f32_bytes,
         "btable_u16_over_f32", unit="bytes_ratio")

    # --- end-to-end query gate ---------------------------------------
    sec, spots = _e2e_query_check(g, part, bl, quick)
    emit("ingest/e2e-query-1e5", sec / QUERY_BATCH * 1e6,
         f"batch={QUERY_BATCH};parity=bitwise;dijkstra_spots={spots}")

    # --- 8-device engine: parity + per-device bytes ceiling ----------
    r = run_json_subprocess(CODE_E8)
    ratio = r["u16_bytes_per_device"] / r["f32_bytes_per_device"]
    assert ratio <= QUANT_BYTES_CEILING, (
        f"quantized per-device resident bytes {ratio:.3f}x float32 at "
        f"E=8 exceeds the {QUANT_BYTES_CEILING}x acceptance ceiling")
    emit("ingest/engine-E8-bytes-f32", r["f32_bytes_per_device"],
         f"n={r['n']};q={r['q']}", unit="bytes",
         config={"backend": r["backend"]})
    emit("ingest/engine-E8-bytes-u16", r["u16_bytes_per_device"],
         f"parity_queries={r['parity_queries']}", unit="bytes",
         config={"backend": r["backend"]})
    emit("ingest/engine-E8-quant-bytes-ratio", ratio,
         f"ceiling={QUANT_BYTES_CEILING}", unit="bytes_ratio",
         config={"backend": r["backend"]})

    if not quick:
        _index_build_point(GRID_FULL, DISTRICT_FULL, "2.5e5")


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="CI smoke: keep the 1e5 end-to-end path, drop "
                         "the 2.5e5 index-build point")
    run(quick=ap.parse_args().quick)
