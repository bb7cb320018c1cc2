"""Batched edge-query engine behind the request plane: parity of
``DistanceService`` against the scalar loop and brute-force search,
across all three §4.2 routing rules, the LB-certified rebuild window,
and unreachable pairs."""
import numpy as np
import pytest

from repro.core import (Partition, bfs_grow_partition,
                        bidirectional_dijkstra, dijkstra, from_edges,
                        grid_road_network, perturb_weights)
from repro.edge import EdgeSystem


@pytest.fixture(scope="module")
def system(small_system):
    # session-scoped shared deploy (tests/conftest.py); read-only —
    # mutating tests deploy their own systems
    return small_system


def test_batched_matches_loop_exactly(system):
    g, part, sys_ = system
    rng = np.random.default_rng(0)
    ss = rng.integers(0, g.num_vertices, size=2000)
    ts = rng.integers(0, g.num_vertices, size=2000)
    np.testing.assert_array_equal(sys_.query_loop(ss, ts),
                                  sys_.service().submit(ss, ts).distances)


def test_batched_matches_brute_force_all_rules(system):
    g, part, sys_ = system
    svc = sys_.service()
    rng = np.random.default_rng(1)
    n = g.num_vertices
    ss = rng.integers(0, n, size=200)
    ts = rng.integers(0, n, size=200)
    # submit half the queries from a rotated client district so rule 2
    # (same district, another server's) fires alongside rules 1 and 3
    client = (part.assignment[ss]
              + rng.integers(0, 2, size=200)) % part.num_districts
    got = svc.submit(ss, ts, client_districts=client).distances
    for i in range(200):
        ref = bidirectional_dijkstra(g, int(ss[i]), int(ts[i]))
        assert got[i] == pytest.approx(ref, rel=1e-5), (ss[i], ts[i])
    assert svc.stats["rule1"] > 0
    assert svc.stats["rule2"] > 0
    assert svc.stats["rule3"] > 0


def test_batched_empty_and_single(system):
    g, part, sys_ = system
    svc = sys_.service()
    empty = svc.submit(np.array([], dtype=np.int64),
                       np.array([], dtype=np.int64))
    assert empty.distances.shape == (0,)
    assert len(empty) == 0 and empty.to_list() == []
    one = svc.submit(np.array([3]), np.array([3]))
    assert one.distances[0] == 0.0
    assert one[0].exact and one[0].rule == 1


def test_rebuild_window_batched_certified_and_exact():
    g = grid_road_network(8, 8, seed=13)
    part = bfs_grow_partition(g, 4, seed=0)
    sys_ = EdgeSystem.deploy(g, part)
    svc = sys_.service()
    rng = np.random.default_rng(2)
    old = svc.plan(np.array([0]), np.array([1])).plane   # pre-window engine
    w2 = perturb_weights(g, rng, lo=0.8, hi=1.3)
    # simulate mid-window: locals refreshed + center rebuilt, shortcuts
    # NOT yet pushed → the batch must go through the Theorem-3 kernels
    g2 = sys_.graph.with_weights(w2)
    sys_.graph = g2
    for srv in sys_.servers:
        srv.refresh_local(g2, part)
    sys_.center.rebuild(w2)
    ss = rng.integers(0, g2.num_vertices, size=400)
    ts = rng.integers(0, g2.num_vertices, size=400)
    plan = svc.plan(ss, ts)
    assert plan.window            # the service planned the fallback plane
    got = plan.execute().distances
    assert svc.stats["lb_fallback_attempts"] > 0
    assert svc.stats["lb_certified"] > 0
    for i in range(0, 400, 7):
        ref = float(dijkstra(g2, int(ss[i]))[int(ts[i])])
        assert got[i] == pytest.approx(ref, rel=1e-5), (ss[i], ts[i])
    # the uncertified residue forced shortcut installs (install_now is
    # the default policy); once every server is fresh again the
    # steady-state engine must agree with the loop
    plan2 = svc.plan(ss, ts)
    assert not plan2.window and plan2.plane is not old
    np.testing.assert_array_equal(plan2.execute().distances,
                                  sys_.query_loop(ss, ts))


def test_engine_parity_mixed_rules_self_pairs_and_clients(system):
    """The engine path == query_loop bit-for-bit on a mixed rule-1/2/3
    batch including s == t pairs and explicit client districts (client
    only affects rule counting, never the answer)."""
    g, part, sys_ = system
    svc = sys_.service()
    rng = np.random.default_rng(5)
    n = g.num_vertices
    ss = rng.integers(0, n, size=1024)
    ts = rng.integers(0, n, size=1024)
    ss[::13] = ts[::13]                       # s == t lanes
    client = (part.assignment[ss]
              + rng.integers(0, 2, size=1024)) % part.num_districts
    loop = sys_.query_loop(ss, ts)
    np.testing.assert_array_equal(
        svc.submit(ss, ts, client_districts=client).distances, loop)
    np.testing.assert_array_equal(svc.submit(ss, ts).distances, loop)
    assert (loop[::13] == 0.0).all()


def test_engine_and_scalar_paths_count_rules_identically():
    g = grid_road_network(8, 8, seed=11)
    part = bfs_grow_partition(g, 4, seed=0)
    rng = np.random.default_rng(6)
    ss = rng.integers(0, g.num_vertices, size=300)
    ts = rng.integers(0, g.num_vertices, size=300)
    client = (part.assignment[ss]
              + rng.integers(0, 2, size=300)) % part.num_districts
    svc_scalar = EdgeSystem.deploy(g, part).service()
    for s, t, c in zip(ss, ts, client):
        svc_scalar.query(int(s), int(t), client_district=int(c))
    sys_engine = EdgeSystem.deploy(g, part)
    svc_engine = sys_engine.service()
    plan = svc_engine.plan(ss, ts, client_districts=client)
    from repro.serve import BucketedPlane
    assert not isinstance(plan.plane, BucketedPlane)  # engine path taken
    assert sys_engine._current_engine() is not None
    plan.execute()
    for k in ("rule1", "rule2", "rule3"):
        assert svc_engine.stats[k] == svc_scalar.stats[k], k
    assert svc_engine.stats["rule2"] > 0


def _two_component_graph():
    """Two disjoint 4x4 unit grids: vertices 0..15 and 16..31."""
    us, vs = [], []
    for base in (0, 16):
        for r in range(4):
            for c in range(4):
                v = base + r * 4 + c
                if c + 1 < 4:
                    us.append(v)
                    vs.append(v + 1)
                if r + 1 < 4:
                    us.append(v)
                    vs.append(v + 4)
    w = np.ones(len(us), dtype=np.float32)
    return from_edges(32, np.array(us), np.array(vs), w)


def test_unreachable_pairs_stay_inf():
    g = _two_component_graph()
    # columns 0-1 → district 0, columns 2-3 → district 1, in BOTH
    # components: every district spans two disconnected pieces
    cols = np.arange(32) % 4
    assignment = np.where(cols < 2, 0, 1).astype(np.int32)
    sys_ = EdgeSystem.deploy(g, Partition(assignment, 2))
    svc = sys_.service()
    ss = np.array([0, 0, 2, 0, 2, 16])
    ts = np.array([16, 19, 17, 5, 3, 31])
    got = svc.submit(ss, ts).distances
    for i in range(len(ss)):
        ref = bidirectional_dijkstra(g, int(ss[i]), int(ts[i]))
        if np.isinf(ref):
            assert np.isinf(got[i]), (ss[i], ts[i])
        else:
            assert got[i] == pytest.approx(ref, rel=1e-5), (ss[i], ts[i])
    # same-district unreachable (rule 1) and cross-district unreachable
    # (rule 3) both surfaced as +inf
    assert np.isinf(got[0]) and np.isinf(got[1])
    rng = np.random.default_rng(3)
    rs = rng.integers(0, 32, size=300)
    rt = rng.integers(0, 32, size=300)
    np.testing.assert_array_equal(sys_.query_loop(rs, rt),
                                  svc.submit(rs, rt).distances)
