"""Sharded (districts→devices) serving == single-process reference.

The 1-device cases run in-process; the 8-device case re-executes this
file's builders in a subprocess with XLA_FLAGS so the main test session
keeps seeing a single CPU device. The 8-device job asserts the full
acceptance contract: ShardedBatchedEngine (both border-table placements)
== replicated BatchedQueryEngine == query_loop bit-for-bit on mixed-rule
batches, the per-device district-table footprint ≤ 1/4 of the replicated
table's, and the B-sharded resident bytes strictly below replicated-B's.
"""
import os
import subprocess
import sys

import numpy as np
import pytest


def _build_case():
    import jax
    from repro.core import (DistanceOracle, bfs_grow_partition,
                            grid_road_network)
    from repro.edge import (default_edge_mesh, pack_for_mesh,
                            prepare_queries, sharded_query)

    g = grid_road_network(8, 8, seed=31)
    part = bfs_grow_partition(g, 4, seed=0)
    oracle = DistanceOracle.build(g, part)
    ndev = len(jax.devices())
    data = pack_for_mesh(part, oracle.border_labels, oracle.local_indexes,
                         ndev)
    mesh = default_edge_mesh(ndev)
    rng = np.random.default_rng(7)
    ss = rng.integers(0, g.num_vertices, size=200)
    ts = rng.integers(0, g.num_vertices, size=200)
    queries = prepare_queries(data, ss, ts)
    got = sharded_query(data, mesh, queries)
    ref = oracle.query_many(ss, ts)
    return got, ref


def _engine_case():
    """ShardedBatchedEngine (both border placements) vs replicated engine
    vs scalar loop on a mixed rule-1/2/3 batch with s == t pairs.
    Returns footprints too."""
    from repro.core import bfs_grow_partition, grid_road_network
    from repro.edge import (BatchedQueryEngine, EdgeSystem,
                            ShardedBatchedEngine)

    g = grid_road_network(10, 10, seed=5)
    part = bfs_grow_partition(g, 8, seed=1)
    system = EdgeSystem.deploy(g, part)
    rng = np.random.default_rng(3)
    ss = rng.integers(0, g.num_vertices, size=600)
    ts = rng.integers(0, g.num_vertices, size=600)
    ss[::17] = ts[::17]                       # s == t lanes
    args = (system.center.border_labels.table,
            [srv.augmented for srv in system.servers],
            part.assignment)
    replicated = BatchedQueryEngine(*args)
    sharded = ShardedBatchedEngine(*args)
    border = ShardedBatchedEngine(*args, shard_border=True)
    return {"rep": replicated.query(ss, ts),
            "shard": sharded.query(ss, ts),
            "bshard": border.query(ss, ts),
            "loop": system.query_loop(ss, ts),
            "auto": system.service().submit(ss, ts).distances,
            "auto_cls": type(system._current_engine()).__name__,
            "per_dev_bytes": sharded.district_table_bytes_per_device(),
            "resident_bytes": sharded.size_bytes(),
            "border_resident_bytes": border.size_bytes(),
            "rep_bytes": replicated.size_bytes(),
            "ndev": sharded.num_devices}


def test_sharded_oracle_single_device_matches():
    got, ref = _build_case()
    np.testing.assert_allclose(got, ref, rtol=1e-5)


def test_sharded_engine_in_process_matches():
    """Runs on however many devices the session exposes (1 in plain
    tier-1, 8 in the mesh CI job); the router must auto-pick the engine
    that matches the backend and answers must agree either way."""
    import jax
    r = _engine_case()
    np.testing.assert_array_equal(r["rep"], r["shard"])
    np.testing.assert_array_equal(r["bshard"], r["shard"])
    np.testing.assert_array_equal(r["shard"], r["loop"])
    expected = ("ShardedBatchedEngine" if len(jax.devices()) > 1
                else "BatchedQueryEngine")
    assert r["auto_cls"] == expected
    np.testing.assert_array_equal(r["auto"], r["loop"])
    # B-sharded resident strictly below replicated-B on a real mesh
    if len(jax.devices()) > 1:
        assert r["border_resident_bytes"] < r["resident_bytes"]


def _run_under_8_devices(code: str) -> None:
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=8")
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src")]
        + env.get("PYTHONPATH", "").split(os.pathsep))
    out = subprocess.run(
        [sys.executable, "-c", code], env=env, capture_output=True,
        text=True, timeout=500,
        cwd=os.path.join(os.path.dirname(__file__), ".."))
    assert out.returncode == 0, out.stderr[-2000:]
    assert "OK8" in out.stdout


@pytest.mark.slow
def test_sharded_oracle_eight_devices_matches():
    _run_under_8_devices(
        "import numpy as np, jax; assert len(jax.devices()) == 8;"
        "import tests.test_sharded_oracle as m;"
        "got, ref = m._build_case();"
        "np.testing.assert_allclose(got, ref, rtol=1e-5);"
        "print('OK8')"
    )


@pytest.mark.slow
def test_sharded_engine_eight_devices_matches_and_shrinks():
    _run_under_8_devices(
        "import numpy as np, jax; assert len(jax.devices()) == 8;"
        "import tests.test_sharded_oracle as m;"
        "r = m._engine_case();"
        "assert r['ndev'] == 8;"
        "np.testing.assert_array_equal(r['rep'], r['shard']);"
        "np.testing.assert_array_equal(r['bshard'], r['shard']);"
        "np.testing.assert_array_equal(r['shard'], r['loop']);"
        "assert r['auto_cls'] == 'ShardedBatchedEngine';"
        "np.testing.assert_array_equal(r['auto'], r['loop']);"
        "assert r['per_dev_bytes'] * 4 <= r['rep_bytes'];"
        "assert r['border_resident_bytes'] < r['resident_bytes'];"
        "print('OK8')"
    )


# ---------------------------------------------------------------------------
# routing: the int32 per-vertex columns against the int64 formula
# ---------------------------------------------------------------------------

def _int64_routing(part, locals_, num_devices, placement, ss, ts):
    """Routing as it was before the per-vertex columns: the layout
    fields derived as packing derived them, then the int64 formula of
    ``prepare_queries`` verbatim."""
    from types import SimpleNamespace
    m = len(locals_)
    if placement is None:
        dpd = -(-m // num_devices)
        ids = np.arange(m, dtype=np.int64)
        device_of, slot_of = ids // dpd, ids % dpd
    else:
        device_of = np.asarray(placement, dtype=np.int64)
        counts = np.bincount(device_of, minlength=num_devices)
        dpd = max(1, int(counts.max()))
        slot_of = np.zeros(m, dtype=np.int64)
        for dev in range(num_devices):
            resident = np.nonzero(device_of == dev)[0]
            slot_of[resident] = np.arange(len(resident))
    kmax = max(len(li.vertices) for li in locals_)
    local_pos = np.zeros(len(part.assignment), dtype=np.int64)
    for li in locals_:
        local_pos[li.vertices] = np.arange(len(li.vertices), dtype=np.int64)
    data = SimpleNamespace(assignment=part.assignment.astype(np.int64),
                           local_pos=local_pos, kmax=kmax,
                           cross_base=dpd * kmax, device_of=device_of,
                           slot_of=slot_of)

    ss = np.asarray(ss, dtype=np.int64)
    ts = np.asarray(ts, dtype=np.int64)
    ds = data.assignment[ss]
    cross = ds != data.assignment[ts]
    # routing reads the packed placement table (blocked default:
    # device i // dpd, slot i % dpd — identical coordinates to the
    # historical arithmetic)
    slot_base = data.slot_of[ds] * data.kmax
    rs = np.where(cross, data.cross_base + ss, slot_base + data.local_pos[ss])
    rt = np.where(cross, data.cross_base + ts, slot_base + data.local_pos[ts])
    return {"owner": data.device_of[ds], "rs": rs, "rt": rt}, data.cross_base


def _routing_batch(system, part, size, seed):
    """s == t, in-district, cross-district and border-vertex lanes."""
    rng = np.random.default_rng(seed)
    n = len(part.assignment)
    ss = rng.integers(0, n, size=size)
    ts = rng.integers(0, n, size=size)
    members = part.districts()
    for i in range(0, size, 3):                   # same district as s
        mem = members[part.assignment[ss[i]]]
        ts[i] = mem[rng.integers(0, len(mem))]
    borders = system.center.border_labels.border_ids.astype(np.int64)
    ss[1::7] = borders[rng.integers(0, len(borders), len(ss[1::7]))]
    ts[2::7] = borders[rng.integers(0, len(borders), len(ts[2::7]))]
    ss[::5] = ts[::5]                             # s == t
    return ss, ts


@pytest.mark.parametrize("size", [1, 255, 256, 257, 65536])
@pytest.mark.parametrize("num_devices,placement,shard_border", [
    (1, "blocked", False), (1, "blocked", True),
    (1, "migrated", False), (1, "migrated", True),
    (4, "blocked", False), (4, "blocked", True),
    (4, "migrated", False), (4, "migrated", True),
])
def test_int32_routing_matches_int64_formula(mesh8_system, size,
                                             num_devices, placement,
                                             shard_border):
    """``prepare_queries`` from the per-vertex int32 columns gives the
    row ids and owners of the int64 formula, for every layout, with
    zero lanes past the batch when asked for a padded length."""
    from repro.edge import pack_tables, prepare_queries
    from repro.kernels.label_join import ops as lj
    from repro.topo import EdgePlacement, RebalancePlanner

    g, part, system = mesh8_system
    locals_ = [srv.augmented for srv in system.servers]
    m = part.num_districts
    host_of = None
    if placement == "migrated":
        planner = RebalancePlanner(EdgePlacement.blocked(m, num_devices),
                                   max_moves=2)
        load = np.ones(m)
        load[planner.placement.districts_of(0)] = 30.0
        planner.observe_load(load)
        plan = planner.plan()
        new = planner.placement if plan is None else plan.placement
        host_of = new.host_of
        if num_devices > 1:       # the migration moved a district
            assert plan is not None
            assert (host_of != EdgePlacement.blocked(m, num_devices)
                    .host_of).any()
    data = pack_tables(system.center.border_labels.table, locals_,
                       part.assignment, num_devices,
                       shard_border=shard_border, placement=host_of)
    data.release_host_tables()          # routing needs no host table
    ss, ts = _routing_batch(system, part, size, seed=size + num_devices)
    ref, cross_base = _int64_routing(part, locals_, num_devices, host_of,
                                     ss, ts)
    assert data.cross_base == cross_base
    qp = lj._ceil_to(size, lj.PAD_Q)
    for length in (None, qp):
        got = prepare_queries(data, ss, ts, length)
        assert set(got) == {"owner", "rs", "rt"}
        for key in ("owner", "rs", "rt"):
            assert got[key].dtype == np.int32, key
            assert len(got[key]) == (size if length is None else length)
            np.testing.assert_array_equal(got[key][:size], ref[key])
            assert not got[key][size:].any(), key


@pytest.fixture(scope="module")
def continent():
    """Integral weights (U{1..15}), so a uint16 spec is lossless and the
    quantized engines answer bit for bit."""
    from repro.core.quantize import fit_label_spec
    from repro.edge import EdgeSystem
    from repro.ingest import synthetic_continent

    csr, part = synthetic_continent(grid=(2, 4), district=(6, 6), seed=5)
    g = csr.to_graph()
    system = EdgeSystem.deploy(g, part)
    args = (system.center.border_labels.table,
            [srv.augmented for srv in system.servers], part.assignment)
    spec = fit_label_spec(args[0], args[1])
    assert spec.lossless
    return g, system, args, spec


@pytest.mark.parametrize("layout", ["replicated", "replicated-uint16",
                                    "sharded", "sharded-border-uint16"])
def test_engine_step_takes_padded_int32_rows(continent, layout,
                                             monkeypatch):
    """Each engine hands its jitted step int32 row ids already padded to
    the PAD_Q bucket (zeros in the pad lanes), answers bit for bit like
    the scalar loop, and batches of one bucket compile one program."""
    from repro.edge import BatchedQueryEngine, ShardedBatchedEngine
    from repro.edge import engine as eng
    from repro.kernels.label_join import ops as lj
    from repro.serve.service import ScalarLoopPlane

    g, system, args, spec = continent
    quant = spec if layout.endswith("uint16") else None
    seen = []

    def recording(step):
        def call(*a, **kw):
            seen.append([x for x in a if isinstance(x, np.ndarray)])
            return step(*a, **kw)
        return call

    if layout.startswith("replicated"):
        engine = BatchedQueryEngine(*args, quant=quant)
        name = "_engine_fn" if quant is None else "_engine_fn_quantized"
        step = getattr(eng, name)
        monkeypatch.setattr(eng, name, recording(step))
        num_rows = 2
    else:
        engine = ShardedBatchedEngine(
            *args, quant=quant, shard_border=layout.startswith(
                "sharded-border"))
        step = engine._fn
        monkeypatch.setattr(engine, "_fn", recording(step))
        num_rows = 3
    step.clear_cache()
    loop = ScalarLoopPlane(system.service())
    rng = np.random.default_rng(5)
    sizes = (300, 301, 2 * lj.PAD_Q - 1)      # one bucket, no multiple
    for qn in sizes:
        ss = rng.integers(0, g.num_vertices, size=qn)
        ts = rng.integers(0, g.num_vertices, size=qn)
        ss[::9] = ts[::9]
        np.testing.assert_array_equal(engine.query(ss, ts),
                                      loop.execute(ss, ts))
        rows = seen[-1]
        assert len(rows) == num_rows
        for r in rows:
            assert r.dtype == np.int32
            assert r.shape == (2 * lj.PAD_Q,)
            assert not r[qn:].any()
    assert len(seen) == len(sizes)
    assert step._cache_size() == 1
