"""Incremental traffic-update subsystem: delta classification, bitwise
repair parity, scoped shortcut invalidation, the live engine swap, and
the scenario-driven simulator epochs."""
import numpy as np
import pytest

from repro.core import (bfs_grow_partition, dijkstra, from_edges,
                        grid_road_network, perturb_weights)
from repro.core.jax_builder import build_border_labels_jax
from repro.edge import (ComputingCenter, EdgeSystem, LatencyModel, Topology,
                        make_trace, run_update_epochs, simulate_centralized,
                        simulate_edge)
from repro.update import (SCENARIOS, IncrementalBuilder, classify_delta,
                          scenario_weights)

SCENARIO_NAMES = sorted(SCENARIOS)


@pytest.fixture(scope="module")
def grid():
    g = grid_road_network(10, 10, seed=11)
    part = bfs_grow_partition(g, 5, seed=0)
    return g, part


# ---------------------------------------------------------------------------
# delta classification
# ---------------------------------------------------------------------------

def test_classify_delta_scopes(grid):
    g, part = grid
    w = g.weights.copy()
    delta = classify_delta(g, part, w)
    assert delta.is_empty and not delta.cross_dirty
    assert len(delta.dirty_districts) == 0

    # dirty one intra-district edge: exactly that district is dirty
    n = g.num_vertices
    src = np.repeat(np.arange(n, dtype=np.int32), np.diff(g.indptr))
    intra = part.assignment[src] == part.assignment[g.indices]
    arc = int(np.nonzero(intra)[0][0])
    u, v = int(src[arc]), int(g.indices[arc])
    w2 = g.weights.copy()
    sel = ((src == u) & (g.indices == v)) | ((src == v) & (g.indices == u))
    w2[sel] *= np.float32(2.0)
    delta = classify_delta(g, part, w2)
    assert delta.num_dirty_edges == 1 and not delta.cross_dirty
    assert delta.dirty_districts.tolist() == [int(part.assignment[u])]

    # dirty one cross edge: no district dirty, overlay dirty
    arc = int(np.nonzero(~intra)[0][0])
    u, v = int(src[arc]), int(g.indices[arc])
    w3 = g.weights.copy()
    sel = ((src == u) & (g.indices == v)) | ((src == v) & (g.indices == u))
    w3[sel] *= np.float32(3.0)
    delta = classify_delta(g, part, w3)
    assert delta.cross_dirty and len(delta.dirty_districts) == 0


def test_classify_delta_rejects_topology_change(grid):
    g, part = grid
    with pytest.raises(ValueError):
        classify_delta(g, part, g.weights[:-2])


def test_apply_delta_rejects_asymmetric_update(grid):
    """An update dirtying only one CSR arc of an edge is invalid — the
    incremental path must reject it like a full rebuild does, not round
    it down to a silent no-op."""
    g, part = grid
    from repro.edge import ComputingCenter as _CC
    center = _CC(g, part, builder="jax")
    center.rebuild()
    w2 = g.weights.copy()
    w2[0] += np.float32(5.0)
    delta = classify_delta(g, part, w2)
    assert not delta.is_empty
    with pytest.raises(ValueError):
        center.apply_delta(w2)


def test_scenarios_terminate_on_disconnected_graphs():
    """Two disconnected triangles: the BFS-ball scenarios must saturate
    the start component and stop instead of spinning forever."""
    from repro.core.partition import Partition
    g = from_edges(6, np.array([0, 1, 2, 3, 4, 5]),
                   np.array([1, 2, 0, 4, 5, 3]),
                   np.ones(6, dtype=np.float32))
    part = Partition(np.array([0, 0, 0, 1, 1, 1], dtype=np.int32), 2)
    rng = np.random.default_rng(0)
    for name in ("incident", "rush_hour"):
        w2 = scenario_weights(name, g, part, rng, 1.0)
        g.with_weights(w2)           # still symmetric


# ---------------------------------------------------------------------------
# bitwise repair parity (the subsystem's core contract)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", SCENARIO_NAMES)
def test_incremental_bitwise_equals_full_rebuild(grid, name):
    g, part = grid
    builder = IncrementalBuilder()
    builder.build_full(g, part)
    rng = np.random.default_rng(3)
    cur = g
    for intensity in (0.01, 0.08):
        w2 = scenario_weights(name, cur, part, rng, intensity)
        g2 = cur.with_weights(w2)
        labels, rep = builder.apply_delta(g2, part,
                                          classify_delta(cur, part, w2))
        full = build_border_labels_jax(g2, part)
        np.testing.assert_array_equal(labels.table, full.table)
        np.testing.assert_array_equal(labels.border_ids, full.border_ids)
        cur = g2


def test_incremental_property_random_deltas(grid):
    """Property: for ANY symmetric weight delta — random fraction, scale,
    direction, applied in sequence — the repaired index is bitwise equal
    to a full rebuild on the new weights."""
    g, part = grid
    builder = IncrementalBuilder()
    builder.build_full(g, part)
    cur = g
    for seed in range(1, 9):
        rng = np.random.default_rng(seed)
        frac = float(rng.uniform(0.002, 0.9))
        lo, hi = sorted(rng.uniform(0.5, 2.0, size=2))
        w2 = perturb_weights(cur, rng, lo=lo, hi=max(hi, lo + 1e-3),
                             frac=frac)
        g2 = cur.with_weights(w2)
        labels, _ = builder.apply_delta(g2, part)
        full = build_border_labels_jax(g2, part)
        np.testing.assert_array_equal(labels.table, full.table)
        cur = g2


def test_incremental_unpruned_variant(grid):
    g, part = grid
    builder = IncrementalBuilder(prune=False)
    builder.build_full(g, part)
    rng = np.random.default_rng(5)
    w2 = scenario_weights("incident", g, part, rng, 0.02)
    g2 = g.with_weights(w2)
    labels, _ = builder.apply_delta(g2, part)
    full = build_border_labels_jax(g2, part, prune=False)
    np.testing.assert_array_equal(labels.table, full.table)


def test_incremental_single_district_empty_border():
    g = grid_road_network(5, 5, seed=2)
    part = bfs_grow_partition(g, 1, seed=0)
    builder = IncrementalBuilder()
    labels = builder.build_full(g, part)
    assert labels.num_borders == 0
    rng = np.random.default_rng(0)
    g2 = g.with_weights(perturb_weights(g, rng))
    labels2, rep = builder.apply_delta(g2, part)
    assert rep["incremental"] and labels2.num_borders == 0


def _pendant_two_block_graph():
    """Two 3×3 grid blocks joined by one cross edge, plus a pendant
    vertex (18) hanging off an interior corner of block 0: changing the
    pendant edge moves no border-to-border distance, so the repair takes
    every warm path (closure reuse + row-scoped re-prune)."""
    us, vs = [], []
    for b in range(2):
        o = 9 * b
        for r in range(3):
            for c in range(3):
                if c + 1 < 3:
                    us.append(o + 3 * r + c); vs.append(o + 3 * r + c + 1)
                if r + 1 < 3:
                    us.append(o + 3 * r + c); vs.append(o + 3 * (r + 1) + c)
    us.append(8); vs.append(9)        # cross edge: borders are 8 and 9
    us.append(0); vs.append(18)       # pendant off vertex 0 (interior)
    w = 1.0 + np.arange(len(us), dtype=np.float32) % 5
    g = from_edges(19, np.array(us), np.array(vs), w)
    assignment = np.array([0] * 9 + [1] * 9 + [0], dtype=np.int32)
    from repro.core.partition import Partition
    return g, Partition(assignment, 2)


def test_incremental_scoped_prune_and_closure_reuse():
    g, part = _pendant_two_block_graph()
    builder = IncrementalBuilder()
    builder.build_full(g, part)
    n = g.num_vertices
    src = np.repeat(np.arange(n, dtype=np.int32), np.diff(g.indptr))
    sel = (src == 18) | (g.indices == np.int32(18))
    w2 = g.weights.copy()
    w2[sel] *= np.float32(4.0)
    g2 = g.with_weights(w2)
    labels, rep = builder.apply_delta(g2, part)
    assert rep["incremental"]
    assert rep["closure_reused"], "pendant edge cannot move the overlay"
    assert rep["repruned_rows"] == 1, "only the pendant row moves"
    assert rep["changed_rows"].sum() == 1 and rep["changed_rows"][18]
    full = build_border_labels_jax(g2, part)
    np.testing.assert_array_equal(labels.table, full.table)


# ---------------------------------------------------------------------------
# ComputingCenter: builder option + scoped invalidation
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dims,m", [((6, 6), 3), ((8, 8), 4)])
def test_center_jax_builder_bitwise_matches_reference(dims, m):
    """`builder="jax"` is a drop-in for the reference builder: on the
    tier-1 grids with integral weights (exact f32 arithmetic) the two
    pipelines produce bit-for-bit the same border-label table."""
    g = grid_road_network(*dims, seed=21)
    g = g.with_weights(np.ceil(g.weights))
    part = bfs_grow_partition(g, m, seed=0)
    ref = ComputingCenter(g, part, builder="reference")
    ref.rebuild()
    jx = ComputingCenter(g, part, builder="jax")
    jx.rebuild()
    np.testing.assert_array_equal(ref.border_labels.table,
                                  jx.border_labels.table)
    for i in range(part.num_districts):
        np.testing.assert_array_equal(ref.shortcuts_for(i),
                                      jx.shortcuts_for(i))


def test_center_apply_delta_scoped_shortcut_invalidation():
    g, part = _pendant_two_block_graph()
    center = ComputingCenter(g, part, builder="jax")
    center.rebuild()
    for i in range(part.num_districts):
        center.shortcuts_for(i)       # populate the cache
    cached = dict(center._shortcut_cache)
    n = g.num_vertices
    src = np.repeat(np.arange(n, dtype=np.int32), np.diff(g.indptr))
    w2 = g.weights.copy()
    w2[(src == 18) | (g.indices == np.int32(18))] *= np.float32(4.0)
    rep = center.apply_delta(w2)
    assert rep["incremental"] and rep["stale_districts"] == []
    # no border row moved: every cached shortcut matrix survives the bump
    assert all(center._shortcut_cache[i] is cached[i]
               for i in range(part.num_districts))
    # a delta through the cross edge moves B rows → scoped invalidation
    w3 = center.graph.weights.copy()
    w3[(src == 8) & (g.indices == np.int32(9))] *= np.float32(2.0)
    w3[(src == 9) & (g.indices == np.int32(8))] *= np.float32(2.0)
    rep = center.apply_delta(w3)
    assert rep["stale_districts"]
    fresh = ComputingCenter(center.graph, part, builder="jax")
    fresh.rebuild()
    for i in range(part.num_districts):
        np.testing.assert_array_equal(center.shortcuts_for(i),
                                      fresh.shortcuts_for(i))


def test_center_apply_delta_noop_keeps_version(grid):
    g, part = grid
    center = ComputingCenter(g, part, builder="jax")
    center.rebuild()
    v = center.version
    rep = center.apply_delta(g.weights.copy())
    assert rep["noop"] and center.version == v


# ---------------------------------------------------------------------------
# EdgeSystem: incremental update cycle + live engine swap
# ---------------------------------------------------------------------------

def test_edge_system_incremental_update_stays_exact(grid):
    g, part = grid
    sys_ = EdgeSystem.deploy(g, part, builder="jax")
    svc = sys_.service()
    rng = np.random.default_rng(7)
    for name in ("incident", "rush_hour"):
        w2 = scenario_weights(name, sys_.graph, part, rng, 0.03)
        timings = sys_.apply_traffic_update(w2, incremental=True)
        assert timings["incremental"]
        g2 = sys_.graph
        for _ in range(25):
            s, t = rng.integers(0, g2.num_vertices, size=2)
            ref = float(dijkstra(g2, int(s))[int(t)])
            got = svc.query(int(s), int(t)).distance
            assert got == pytest.approx(ref, rel=1e-5), (s, t)


def test_edge_system_clean_districts_keep_serving():
    g, part = _pendant_two_block_graph()
    sys_ = EdgeSystem.deploy(g, part, builder="jax")
    before = [srv.augmented for srv in sys_.servers]
    n = g.num_vertices
    src = np.repeat(np.arange(n, dtype=np.int32), np.diff(g.indptr))
    w2 = g.weights.copy()
    w2[(src == 18) | (g.indices == np.int32(18))] *= np.float32(4.0)
    timings = sys_.apply_traffic_update(w2, incremental=True)
    # district 1 is untouched: same L_1⁺ object, no rebuild window, and
    # the version bump is adopted in place
    assert timings["dirty_districts"] == [0]
    assert timings["clean_districts"] == [1]
    assert sys_.servers[1].augmented is before[1]
    assert sys_.servers[1].augmented_version == sys_.center.version
    assert sys_.current_engine() is not None
    g2 = sys_.graph
    rng = np.random.default_rng(1)
    ss = rng.integers(0, n, size=64)
    ts = rng.integers(0, n, size=64)
    ref = np.array([dijkstra(g2, int(s))[int(t)] for s, t in zip(ss, ts)],
                   dtype=np.float32)
    np.testing.assert_allclose(sys_.service().submit(ss, ts).distances,
                               ref, rtol=1e-5)


def test_rebuild_window_parity_while_update_midflight(grid):
    """Mid-flight: dirty districts refreshed their plain L_i and the
    center repaired B, but no shortcuts are installed yet. Every answer
    must still be exact on the NEW weights (Theorem-3 certificate or
    wait-for-push) — never stale."""
    g, part = grid
    sys_ = EdgeSystem.deploy(g, part, builder="jax")
    rng = np.random.default_rng(9)
    w2 = scenario_weights("regional", g, part, rng, 0.2)
    rep = sys_.center.apply_delta(w2)
    g2 = sys_.center.graph
    sys_.graph = g2
    for i in rep["delta"].dirty_districts:
        sys_.servers[int(i)].refresh_local(g2, part)
    for i in rep["stale_districts"]:
        sys_.servers[i].augmented = None      # shortcut push still pending
    assert sys_.current_engine() is None      # rebuild window is open
    svc = sys_.service()
    checked = 0
    while checked < 25:
        s, t = rng.integers(0, g2.num_vertices, size=2)
        ref = float(dijkstra(g2, int(s))[int(t)])
        res = svc.query(int(s), int(t))
        assert res.distance == pytest.approx(ref, rel=1e-5), (s, t)
        assert res.exact
        checked += 1
    assert svc.stats["lb_fallback_attempts"] > 0
    # batched path mid-flight, then the window closes and the engine swaps
    ss = rng.integers(0, g2.num_vertices, size=48)
    ts = rng.integers(0, g2.num_vertices, size=48)
    ref = np.array([dijkstra(g2, int(s))[int(t)] for s, t in zip(ss, ts)],
                   dtype=np.float32)
    np.testing.assert_allclose(svc.submit(ss, ts).distances, ref, rtol=1e-5)
    assert sys_.current_engine() is not None


def test_engine_layouts_bitwise_after_incremental_update(grid):
    """After an incremental update the swapped engine serves bit-for-bit
    the same answers in every layout — replicated, district-sharded, and
    row-sharded B (q-width) — on however many devices the backend
    exposes (8 virtual devices in the tier1-mesh8 CI job)."""
    g, part = grid
    sys_ = EdgeSystem.deploy(g, part, builder="jax")
    rng = np.random.default_rng(13)
    ss = rng.integers(0, g.num_vertices, size=256)
    ts = rng.integers(0, g.num_vertices, size=256)
    from repro.serve import ServingPolicy
    services = [sys_.service(ServingPolicy(engine=engine,
                                           shard_border=border))
                for engine, border in (("replicated", None),
                                       ("sharded", False),
                                       ("sharded", True))]
    before = sys_.query_loop(ss, ts)
    old = []
    for svc in services:           # each service serves the old version
        np.testing.assert_array_equal(svc.submit(ss, ts).distances, before)
        old.append(svc.plan(ss, ts).plane)
    w2 = scenario_weights("rush_hour", g, part, rng, 0.05)
    sys_.apply_traffic_update(w2, incremental=True)
    ref = sys_.query_loop(ss, ts)
    assert not np.array_equal(ref, before)
    for svc, engine in zip(services, old):
        # the same service swaps to the new version's engine
        np.testing.assert_array_equal(svc.submit(ss, ts).distances, ref)
        assert svc.plan(ss, ts).plane is not engine


def test_service_forwards_client_districts_and_kernels(grid):
    g, part = grid
    sys_ = EdgeSystem.deploy(g, part)
    from repro.serve import ServingPolicy
    # same-district pairs observed from another district are rule 2
    ds = part.assignment
    s = int(np.nonzero(ds == 0)[0][0])
    t = int(np.nonzero(ds == 0)[0][1])
    ss = np.array([s]); ts = np.array([t])
    other = np.array([1], dtype=np.int32)
    svc = sys_.service(ServingPolicy(use_kernels=False))
    out = svc.submit(ss, ts, client_districts=other).distances
    assert svc.stats["rule2"] == 1
    ref = float(dijkstra(g, s)[t])
    assert out[0] == pytest.approx(ref, rel=1e-5)
    np.testing.assert_allclose(
        sys_.service().submit(ss, ts, client_districts=other).distances,
        out, rtol=1e-6)


# ---------------------------------------------------------------------------
# scenario generators + simulator epochs
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", SCENARIO_NAMES)
def test_scenarios_are_symmetric_and_sized(grid, name):
    g, part = grid
    rng = np.random.default_rng(17)
    w2 = scenario_weights(name, g, part, rng, 0.05)
    g2 = g.with_weights(w2)          # raises if asymmetric
    delta = classify_delta(g, part, w2)
    assert not delta.is_empty
    assert (w2 > 0).all()
    if name in ("incident", "jitter"):      # exact dirty-count control
        assert delta.num_dirty_edges == max(1, round(0.05 * g.num_edges))
    assert g2.num_edges == g.num_edges


def test_run_update_epochs_and_variable_schedule(grid):
    g, part = grid
    sys_ = EdgeSystem.deploy(g, part, builder="jax")
    schedule, reports = run_update_epochs(sys_, "incident", 2, 4000.0,
                                          seed=3, intensity=0.02)
    assert len(reports) == 2
    assert all(r["full_rebuild_s"] > 0 for r in reports)
    assert all(r["bl_rebuild_s"] >= 0 for r in reports)
    # before the first epoch both deployments are fresh
    assert schedule.fresh_at_centralized(10.0) == 10.0
    assert schedule.edge_windows(10.0) == (0.0, 0.0)
    lr, gr = schedule.edge_windows(4000.5)
    assert 4000.0 <= lr <= gr
    trace = make_trace(sys_.graph, 400, horizon_ms=12000.0, seed=5)
    topo = Topology(part.num_districts, LatencyModel())
    edge = simulate_edge(trace, topo, schedule, part.assignment,
                         lambda s, t: True, part.num_districts)
    central = simulate_centralized(trace, topo, schedule)
    assert np.isfinite(edge.mean_ms) and np.isfinite(central.mean_ms)
    assert edge.mean_ms < central.mean_ms     # same-district traffic stays
    # every window is anchored at its epoch start
    assert (schedule.local_ready >= schedule.epoch_starts).all()
    assert (schedule.global_ready >= schedule.epoch_starts).all()
    assert (schedule.centralized_ready >= schedule.epoch_starts).all()
