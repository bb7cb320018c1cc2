"""Natural-width + row-sharded border table B: parity and footprint.

The border table used to be stored padded to the combined width
W = max(kmax, q) and replicated on every device. This suite pins down
the two layout changes that retire that:

* natural width — B stored at (n, q); the (batch, q) gathered rows are
  inf-padded to W inside ``join_sharded_gathered``, which must be
  bit-for-bit identical to the stored-at-W path (inf lanes never win a
  min-plus join);
* row-sharding — ``ShardedBatchedEngine(shard_border=True)`` keeps only
  a ceil(n/E) row-slice of B per device and assembles the touched rows
  with a ragged gather + pmin, again bit-for-bit identical.

Coverage: mixed §4.2 rules, s == t lanes, border-vertex endpoints, the
router's ``shard_border`` override + auto heuristic, and the q == 0
single-district edge case.
"""
import dataclasses

import numpy as np
import pytest

from repro.core import (DistanceOracle, bfs_grow_partition,
                        grid_road_network)
from repro.edge import (BatchedQueryEngine, EdgeSystem,
                        ShardedBatchedEngine, default_edge_mesh,
                        pack_for_mesh, prepare_queries, sharded_query)


@pytest.fixture(scope="module")
def system(mesh8_system):
    # session-scoped shared deploy (tests/conftest.py); read-only —
    # mutating tests deploy their own systems
    return mesh8_system


def _mixed_batch(g, system, rng, size=600):
    """Mixed rule-1/2/3 batch with s == t lanes and explicit
    border-vertex endpoints (their B rows contain the 0-distance
    self-entry — the hardest rows to get wrong in a resharded layout)."""
    ss = rng.integers(0, g.num_vertices, size=size)
    ts = rng.integers(0, g.num_vertices, size=size)
    borders = system.center.border_labels.border_ids.astype(np.int64)
    k = min(len(borders), len(ss[1::23]), len(ts[2::23]))
    if k:
        ss[1::23][:k] = borders[:k]                   # border endpoints
        ts[2::23][:k] = borders[len(borders) - k:]
    ss[::17] = ts[::17]                               # s == t lanes
    return ss, ts


def _engines(system, part):
    args = (system.center.border_labels.table,
            [srv.augmented for srv in system.servers], part.assignment)
    return (BatchedQueryEngine(*args),
            ShardedBatchedEngine(*args),
            ShardedBatchedEngine(*args, shard_border=True))


def test_natural_width_bitwise_equals_stored_at_w(system):
    """The q-width B (padded per-batch inside join_sharded_gathered)
    must be bit-for-bit identical to a B stored padded to W."""
    g, part, sys_ = system
    oracle = DistanceOracle.build(g, part)
    import jax
    ndev = len(jax.devices())
    data_q = pack_for_mesh(part, oracle.border_labels,
                           oracle.local_indexes, ndev)
    assert data_q.border_width == oracle.border_labels.num_borders
    # stored-at-W variant: same rows, inf lanes materialized in storage
    bt_w = np.full((data_q.btable.shape[0], data_q.width), np.inf,
                   dtype=np.float32)
    bt_w[:, :data_q.border_width] = data_q.btable
    data_w = dataclasses.replace(data_q, btable=bt_w)
    assert data_w.border_width == data_q.width
    mesh = default_edge_mesh(ndev)
    rng = np.random.default_rng(7)
    ss, ts = _mixed_batch(g, sys_, rng, size=400)
    queries = prepare_queries(data_q, ss, ts)
    got_q = sharded_query(data_q, mesh, queries)
    got_w = sharded_query(data_w, mesh, queries)
    np.testing.assert_array_equal(got_q, got_w)
    np.testing.assert_allclose(got_q, oracle.query_many(ss, ts), rtol=1e-5)


def test_border_sharded_engine_parity(system):
    """All three layouts answer identically to the scalar loop on mixed
    rules, s == t, and border-vertex endpoints (1 device in plain tier-1,
    8 in the mesh CI job)."""
    g, part, sys_ = system
    rng = np.random.default_rng(3)
    ss, ts = _mixed_batch(g, sys_, rng)
    replicated, sharded, border = _engines(sys_, part)
    loop = sys_.query_loop(ss, ts)
    np.testing.assert_array_equal(replicated.query(ss, ts), loop)
    np.testing.assert_array_equal(sharded.query(ss, ts), loop)
    np.testing.assert_array_equal(border.query(ss, ts), loop)
    assert (loop[::17] == 0.0).all()
    # single-lane batches: an s == t lane, a border endpoint, a cross lane
    cross = np.nonzero(part.assignment[ss] != part.assignment[ts])[0]
    for i in (0, 1, int(cross[0])):
        for eng in (replicated, sharded, border):
            np.testing.assert_array_equal(
                eng.query(ss[i:i + 1], ts[i:i + 1]), loop[i:i + 1])


def test_border_sharded_footprint_formulas(system):
    """resident_bytes helpers match the documented memory model:
    district dpd·kmax·W·4 per device, B n·q·4 replicated vs
    ceil(n/E)·q·4 sharded (docs/ARCHITECTURE.md table)."""
    g, part, sys_ = system
    _, sharded, border = _engines(sys_, part)
    E = sharded.num_devices
    n = g.num_vertices
    q = sys_.center.border_labels.num_borders
    d = sharded.data
    assert d.width == max(d.kmax, q, 1)
    assert (sharded.district_table_bytes_per_device()
            == d.districts_per_device * d.kmax * d.width * 4)
    assert sharded.border_table_bytes_per_device() == n * q * 4
    assert border.border_table_bytes_per_device() == -(-n // E) * q * 4
    for eng in (sharded, border):
        assert eng.size_bytes() == (eng.district_table_bytes_per_device()
                                    + eng.border_table_bytes_per_device())
    if E > 1:
        assert border.size_bytes() < sharded.size_bytes()
    else:
        assert border.size_bytes() == sharded.size_bytes()


def test_router_shard_border_override_and_auto(system):
    g, part, sys_ = system
    rng = np.random.default_rng(9)
    ss, ts = _mixed_batch(g, sys_, rng, size=300)
    loop = sys_.query_loop(ss, ts)
    from repro.serve import ServingPolicy
    forced = ServingPolicy(engine="sharded", shard_border=True)
    auto = ServingPolicy(engine="sharded")
    try:
        np.testing.assert_array_equal(
            sys_.service(forced).submit(ss, ts).distances, loop)
        eng = sys_._current_engine(forced)
        assert isinstance(eng, ShardedBatchedEngine) and eng.shard_border
        # auto heuristic: a toy B is far below SHARD_BORDER_AUTO_BYTES,
        # so None must resolve to the replicated-B sharded engine
        np.testing.assert_array_equal(
            sys_.service(auto).submit(ss, ts).distances, loop)
        eng = sys_._current_engine(auto)
        assert isinstance(eng, ShardedBatchedEngine)
        assert not eng.shard_border
        # the service plans onto the engine of its own policy's layout
        svc = sys_.service(forced)
        np.testing.assert_array_equal(svc.submit(ss, ts).distances, loop)
        eng = svc.plan(ss, ts).plane
        assert isinstance(eng, ShardedBatchedEngine) and eng.shard_border
    finally:
        sys_._engine = sys_._engine_key = None


def test_single_district_no_borders():
    """q == 0: one district, no border vertices, every query rule 1 —
    the B shard is a (n_pad, 0) array and must stay inert."""
    g = grid_road_network(5, 5, seed=2)
    part = bfs_grow_partition(g, 1, seed=0)
    sys_ = EdgeSystem.deploy(g, part)
    assert sys_.center.border_labels.num_borders == 0
    rng = np.random.default_rng(4)
    ss = rng.integers(0, g.num_vertices, size=128)
    ts = rng.integers(0, g.num_vertices, size=128)
    loop = sys_.query_loop(ss, ts)
    _, sharded, border = _engines(sys_, part)
    np.testing.assert_array_equal(sharded.query(ss, ts), loop)
    np.testing.assert_array_equal(border.query(ss, ts), loop)
    assert border.border_table_bytes_per_device() == 0


def test_empty_batch_all_layouts(system):
    g, part, sys_ = system
    empty = np.array([], dtype=np.int64)
    for eng in _engines(sys_, part):
        out = eng.query(empty, empty)
        assert out.shape == (0,) and out.dtype == np.float32
