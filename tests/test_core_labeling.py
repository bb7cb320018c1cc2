"""PLL, Border Labeling (Thm 1), shortcuts (Thm 2), local bound (Thm 3)."""
import numpy as np
import pytest

from repro.core import (DistanceOracle, bfs_grow_partition, borders_of,
                        build_all_local_indexes,
                        build_border_labels_hierarchical,
                        build_border_labels_reference, certified_local_query,
                        dijkstra, grid_road_network, local_bound, pll,
                        query_batch, random_geometric_network, Rule, route)


def small_graphs():
    return [
        grid_road_network(6, 6, seed=0),
        grid_road_network(7, 5, seed=2, highway_frac=0.05),
        random_geometric_network(80, seed=4),
    ]


# ---------------------------------------------------------------------------
# PLL (§2.1)
# ---------------------------------------------------------------------------

def test_pll_is_exact_2hop_cover():
    for g in small_graphs():
        labels = pll(g)
        rng = np.random.default_rng(0)
        ss = rng.integers(0, g.num_vertices, size=40)
        ts = rng.integers(0, g.num_vertices, size=40)
        got = labels.query_many(ss, ts)
        for s, t, d in zip(ss, ts, got):
            ref = dijkstra(g, int(s))[int(t)]
            assert d == pytest.approx(float(ref), rel=1e-5), (s, t)


def test_pll_prunes_labels():
    g = grid_road_network(8, 8, seed=1)
    labels = pll(g)
    # pruning must keep the average label far below n
    assert labels.label_sizes().mean() < g.num_vertices / 4


# ---------------------------------------------------------------------------
# Border Labeling (§3.1, Theorem 1)
# ---------------------------------------------------------------------------

def test_theorem1_cross_district_and_border_queries():
    for g in small_graphs():
        part = bfs_grow_partition(g, 4, seed=0)
        bl = build_border_labels_reference(g, part)
        borders = np.concatenate(borders_of(g, part))
        rng = np.random.default_rng(1)
        # constraint 2: endpoints in different districts
        checked = 0
        while checked < 30:
            s, t = rng.integers(0, g.num_vertices, size=2)
            if part.assignment[s] == part.assignment[t]:
                continue
            ref = dijkstra(g, int(s))[int(t)]
            assert bl.query(int(s), int(t)) == pytest.approx(
                float(ref), rel=1e-5)
            checked += 1
        # constraint 1: both endpoints are borders (same district too)
        for _ in range(20):
            s, t = rng.choice(borders, size=2)
            ref = dijkstra(g, int(s))[int(t)]
            assert bl.query(int(s), int(t)) == pytest.approx(
                float(ref), rel=1e-5)


def test_border_label_width_bounded_by_border_count():
    g = grid_road_network(8, 8, seed=0)
    part = bfs_grow_partition(g, 4, seed=0)
    bl = build_border_labels_reference(g, part)
    assert bl.label_sizes().max() <= bl.num_borders


def test_hierarchical_builder_matches_reference():
    for g in small_graphs():
        part = bfs_grow_partition(g, 3, seed=0)
        ref = build_border_labels_reference(g, part)
        hier = build_border_labels_hierarchical(g, part)
        assert ref.num_borders == hier.num_borders
        rng = np.random.default_rng(2)
        ss = rng.integers(0, g.num_vertices, size=60)
        ts = rng.integers(0, g.num_vertices, size=60)
        np.testing.assert_allclose(ref.query_many(ss, ts),
                                   hier.query_many(ss, ts), rtol=1e-5)


def test_hierarchical_prune_matches_reference_labels_exactly():
    # integer weights -> exact arithmetic -> identical prune decisions
    g = grid_road_network(6, 6, seed=5)
    g = g.with_weights(np.ceil(g.weights))
    part = bfs_grow_partition(g, 3, seed=1)
    ref = build_border_labels_reference(g, part)
    hier = build_border_labels_hierarchical(g, part)
    np.testing.assert_array_equal(np.isfinite(ref.table),
                                  np.isfinite(hier.table))
    np.testing.assert_allclose(
        np.nan_to_num(ref.table, posinf=-1),
        np.nan_to_num(hier.table, posinf=-1), rtol=1e-6)


def test_unpruned_hierarchical_is_superset():
    g = grid_road_network(6, 6, seed=7)
    part = bfs_grow_partition(g, 3, seed=0)
    pruned = build_border_labels_hierarchical(g, part, prune=True)
    full = build_border_labels_hierarchical(g, part, prune=False)
    keep_p = np.isfinite(pruned.table)
    keep_f = np.isfinite(full.table)
    assert np.all(keep_f | ~keep_p)          # pruned ⊆ full
    assert keep_f.sum() >= keep_p.sum()


# ---------------------------------------------------------------------------
# Shortcuts + local indexes (§3.2, Theorem 2)
# ---------------------------------------------------------------------------

def test_theorem2_same_district_queries_exact():
    for g in small_graphs():
        part = bfs_grow_partition(g, 4, seed=0)
        bl = build_border_labels_reference(g, part)
        locals_ = build_all_local_indexes(g, part, bl=bl)
        rng = np.random.default_rng(3)
        checked = 0
        while checked < 30:
            s, t = rng.integers(0, g.num_vertices, size=2)
            i = part.assignment[s]
            if i != part.assignment[t]:
                continue
            idx = locals_[int(i)]
            sl = int(idx.local_of(np.array([s]))[0])
            tl = int(idx.local_of(np.array([t]))[0])
            ref = dijkstra(g, int(s))[int(t)]
            assert idx.query_local(sl, tl) == pytest.approx(
                float(ref), rel=1e-5), (s, t)
            checked += 1


def test_plain_local_index_is_district_exact_but_global_upper_bound():
    g = grid_road_network(7, 7, seed=9, highway_frac=0.04)
    part = bfs_grow_partition(g, 4, seed=2)
    locals_plain = build_all_local_indexes(g, part, bl=None)
    rng = np.random.default_rng(5)
    checked = 0
    while checked < 30:
        s, t = rng.integers(0, g.num_vertices, size=2)
        i = part.assignment[s]
        if i != part.assignment[t]:
            continue
        idx = locals_plain[int(i)]
        sl = int(idx.local_of(np.array([s]))[0])
        tl = int(idx.local_of(np.array([t]))[0])
        lam = idx.query_local(sl, tl)
        ref = float(dijkstra(g, int(s))[int(t)])
        assert lam >= ref - 1e-4  # never below the true distance
        checked += 1


# ---------------------------------------------------------------------------
# Local bound (Definition 5, Theorem 3)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("gi", range(3))
def test_border_dist_matches_one_query_many_per_border(gi):
    """The min-plus ``border_dist`` against the loop it replaced, bit for
    bit, on plain and shortcut-augmented indexes (non-integral weights
    included)."""
    g = small_graphs()[gi]
    part = bfs_grow_partition(g, 4, seed=1)
    bl = build_border_labels_reference(g, part)
    for idx in (build_all_local_indexes(g, part, bl=None)
                + build_all_local_indexes(g, part, bl=bl)):
        k = len(idx.vertices)
        ref = np.full((k, len(idx.border_locals)), np.inf, np.float32)
        for j, b in enumerate(idx.border_locals):
            ref[:, j] = idx.labels.query_many(np.arange(k),
                                              np.full(k, int(b)))
        assert idx.border_dist.dtype == np.float32
        assert np.array_equal(idx.border_dist.view(np.uint32),
                              ref.view(np.uint32))


def test_theorem3_certified_answers_are_exact():
    for g in small_graphs():
        part = bfs_grow_partition(g, 4, seed=0)
        locals_plain = build_all_local_indexes(g, part, bl=None)
        rng = np.random.default_rng(7)
        certified = 0
        for _ in range(300):
            s, t = rng.integers(0, g.num_vertices, size=2)
            i = part.assignment[s]
            if i != part.assignment[t]:
                continue
            d, ok = certified_local_query(locals_plain[int(i)], int(s), int(t))
            if ok:
                ref = float(dijkstra(g, int(s))[int(t)])
                assert d == pytest.approx(ref, rel=1e-5), (s, t)
                certified += 1
        assert certified > 0  # the bound must certify a nontrivial share


# ---------------------------------------------------------------------------
# Routing + end-to-end oracle
# ---------------------------------------------------------------------------

def test_routing_rules():
    assert route(2, 2, 2) == Rule.LOCAL
    assert route(1, 1, 2) == Rule.FORWARD_EDGE
    assert route(0, 3, 0) == Rule.CROSS


@pytest.mark.parametrize("builder", ["reference", "hierarchical"])
def test_oracle_end_to_end(builder):
    g = grid_road_network(8, 8, seed=11)
    part = bfs_grow_partition(g, 4, seed=0)
    oracle = DistanceOracle.build(g, part, builder=builder)
    rng = np.random.default_rng(8)
    ss = rng.integers(0, g.num_vertices, size=50)
    ts = rng.integers(0, g.num_vertices, size=50)
    got = oracle.query_many(ss, ts)
    for s, t, d in zip(ss, ts, got):
        ref = float(dijkstra(g, int(s))[int(t)])
        assert d == pytest.approx(ref, rel=1e-5), (s, t)
    assert oracle.stats.bl_seconds > 0
    assert oracle.stats.num_borders > 0


def test_oracle_rebuild_reflects_weight_updates():
    g = grid_road_network(6, 6, seed=13)
    part = bfs_grow_partition(g, 3, seed=0)
    oracle = DistanceOracle.build(g, part)
    w2 = g.weights * 3.0
    oracle2 = oracle.rebuild(w2)
    g2 = g.with_weights(w2)
    rng = np.random.default_rng(9)
    for _ in range(15):
        s, t = rng.integers(0, g.num_vertices, size=2)
        ref = float(dijkstra(g2, int(s))[int(t)])
        assert oracle2.query(int(s), int(t)) == pytest.approx(ref, rel=1e-5)
