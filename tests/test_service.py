"""Request-plane contract: DistanceService / ServingPolicy / QueryPlane.

Pins the api_redesign acceptance criteria:

* bit-for-bit parity of the service front door with the scalar loop
  across all three engine placements (1 device in plain tier-1, 8 in
  the tier1-mesh8 CI job, plus a subprocess-forced 8-device case);
* the three rebuild-window modes agree wherever the Theorem-3
  certificate fires, and ``stale_ok`` flags its residue non-exact;
* rule counters live in per-result metadata — batcher padding dummies
  are excluded (the old ``EdgeSystem.stats`` inflation wart);
* the PR-5 deprecated ``EdgeSystem.query*`` shims are GONE (two PRs of
  ``-W error::DeprecationWarning`` guard, then removal);
* ``DistanceBatcher`` accepts any ``QueryPlane`` and rejects
  non-engines with a clear ``TypeError``.
"""
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.core import bfs_grow_partition, grid_road_network, perturb_weights
from repro.edge import BatchedQueryEngine, EdgeSystem, ShardedBatchedEngine
from repro.serve import (CERTIFY_OR_WAIT, STALE_OK, BucketedPlane,
                         DistanceBatcher, DistanceService, QueryPlane,
                         QueryRequest, ScalarLoopPlane, ServingPolicy)


@pytest.fixture(scope="module")
def system(mesh8_system):
    # session-scoped shared deploy (tests/conftest.py); read-only —
    # mutating tests below deploy their own systems
    return mesh8_system


def _batch(g, rng, size=512):
    ss = rng.integers(0, g.num_vertices, size=size)
    ts = rng.integers(0, g.num_vertices, size=size)
    ss[::17] = ts[::17]                               # s == t lanes
    return ss, ts


# ---------------------------------------------------------------------------
# parity across engine placements
# ---------------------------------------------------------------------------

def test_service_parity_all_engine_placements(system):
    """DistanceService answers == scalar loop bit-for-bit under every
    ServingPolicy placement (replicated / district-sharded / B-sharded /
    auto) — on 1 device in plain tier-1, 8 in the mesh8 CI job."""
    g, part, sys_ = system
    rng = np.random.default_rng(3)
    ss, ts = _batch(g, rng)
    loop = sys_.query_loop(ss, ts)
    policies = [ServingPolicy(),                       # auto
                ServingPolicy(engine="replicated"),
                ServingPolicy(engine="sharded", shard_border=False),
                ServingPolicy(engine="sharded", shard_border=True),
                ServingPolicy(use_kernels=False)]      # bucketed reference
    for pol in policies:
        got = sys_.service(pol).submit(ss, ts)
        np.testing.assert_array_equal(got.distances, loop), pol
        assert got.exact.all() and not got.fallback.any()
    # the sharded placements really selected the sharded engine
    svc = sys_.service(ServingPolicy(engine="sharded", shard_border=True))
    plane = svc.plan(ss, ts).plane
    assert isinstance(plane, ShardedBatchedEngine) and plane.shard_border
    plane = sys_.service(ServingPolicy(engine="replicated")).plan(ss,
                                                                  ts).plane
    assert isinstance(plane, BatchedQueryEngine)


def test_planes_satisfy_query_plane_protocol(system):
    g, part, sys_ = system
    svc = sys_.service()
    rng = np.random.default_rng(4)
    ss, ts = _batch(g, rng, size=64)
    planes = [svc.plan(ss, ts).plane, svc.scalar_plane(),
              BucketedPlane(svc)]
    ref = None
    for plane in planes:
        assert isinstance(plane, QueryPlane)
        out = np.asarray(plane.execute(ss, ts))
        ref = out if ref is None else ref
        np.testing.assert_array_equal(out, ref)


def test_typed_request_round_trip(system):
    g, part, sys_ = system
    svc = sys_.service()
    ds = part.assignment
    s0 = int(np.nonzero(ds == 0)[0][0])
    t0 = int(np.nonzero(ds == 0)[0][1])
    s1 = int(np.nonzero(ds == 1)[0][0])
    reqs = [QueryRequest(s0, t0),                      # rule 1
            QueryRequest(s0, t0, client_district=1),   # rule 2
            QueryRequest(s0, s1)]                      # rule 3
    out = svc.submit_requests(reqs)
    assert [int(r.rule) for r in out] == [1, 2, 3]
    assert all(r.exact and r.exactness == "exact" for r in out)
    assert all(r.index_version == sys_.center.version for r in out)
    assert all(r.latency_s >= 0 for r in out)
    loop = sys_.query_loop(np.array([r.s for r in reqs]),
                           np.array([r.t for r in reqs]))
    np.testing.assert_array_equal(
        np.array([r.distance for r in out], dtype=np.float32), loop)
    assert svc.submit_requests([]) == []


def test_serving_policy_validation():
    with pytest.raises(ValueError, match="engine"):
        ServingPolicy(engine="hybrid")
    with pytest.raises(ValueError, match="rebuild"):
        ServingPolicy(rebuild="yolo")


# ---------------------------------------------------------------------------
# rule counters: per-result metadata, padding excluded
# ---------------------------------------------------------------------------

def test_padded_batcher_counters_match_scalar(system):
    """Regression for the stats-inflation wart: rid=-1 padding dummies
    from DistanceBatcher must NOT be counted — engine-path counters under
    a padded batcher equal the scalar path's on the same requests."""
    g, part, sys_ = system
    rng = np.random.default_rng(7)
    ss, ts = _batch(g, rng, size=70)          # 70 % 32 != 0 → padded tail
    svc_scalar = sys_.service()
    for s, t in zip(ss, ts):
        svc_scalar.query(int(s), int(t))
    svc_batched = sys_.service()
    batcher = DistanceBatcher(svc_batched, batch_size=32, pad=True)
    batcher.submit_pairs(list(zip(ss.tolist(), ts.tolist())))
    done = batcher.run()
    assert len(done) == 70
    assert svc_batched.stats == svc_scalar.stats
    total = sum(svc_batched.stats[k] for k in ("rule1", "rule2", "rule3"))
    assert total == 70                        # dummies would make it 96
    np.testing.assert_array_equal(
        np.array([r.distance for r in done], dtype=np.float32),
        sys_.query_loop(ss, ts))


def test_result_batch_real_mask_and_counters(system):
    g, part, sys_ = system
    svc = sys_.service()
    rng = np.random.default_rng(8)
    ss, ts = _batch(g, rng, size=16)
    real = np.ones(16, dtype=bool)
    real[10:] = False
    batch = svc.submit(ss, ts, real=real)
    counters = batch.counters()
    assert sum(counters[k] for k in ("rule1", "rule2", "rule3")) == 10
    assert svc.stats == counters
    # metadata still covers ALL rows; only counters are masked
    assert len(batch) == 16 and batch.exact.all()


# ---------------------------------------------------------------------------
# DistanceBatcher engine resolution
# ---------------------------------------------------------------------------

def test_batcher_rejects_non_engines_with_clear_typeerror():
    with pytest.raises(TypeError, match="query_batched/query/execute"):
        DistanceBatcher(object())
    with pytest.raises(TypeError, match="DistanceService"):
        DistanceBatcher(42)


def test_batcher_accepts_query_plane_and_edge_system(system):
    g, part, sys_ = system
    svc = sys_.service()
    rng = np.random.default_rng(9)
    ss, ts = _batch(g, rng, size=48)
    ref = sys_.query_loop(ss, ts)
    pairs = list(zip(ss.tolist(), ts.tolist()))
    # a raw engine snapshot is a QueryPlane (execute): plugs in directly
    plane = svc.plan(ss, ts).plane
    for engine in (plane, svc.scalar_plane()):
        b = DistanceBatcher(engine, batch_size=16, pad=False)
        b.submit_pairs(pairs)
        got = np.array([r.distance for r in b.run()], dtype=np.float32)
        np.testing.assert_array_equal(got, ref)
    # an EdgeSystem is wrapped in its own service (padding-masked)
    b = DistanceBatcher(sys_, batch_size=32, pad=True)
    assert isinstance(b.service, DistanceService)
    b.submit_pairs(pairs)
    got = np.array([r.distance for r in b.run()], dtype=np.float32)
    np.testing.assert_array_equal(got, ref)
    assert sum(b.service.stats[k]
               for k in ("rule1", "rule2", "rule3")) == len(pairs)


def test_service_batcher_helper_uses_policy_batch_size(system):
    from repro.edge import BatchPolicy
    g, part, sys_ = system
    svc = sys_.service(ServingPolicy(batch=BatchPolicy(batch_size=17)))
    b = svc.batcher()
    assert b.batch_size == 17 and b.service is svc
    assert sys_.service().batcher(batch_size=9).batch_size == 9


# ---------------------------------------------------------------------------
# deprecated shims: removed after their two-PR deprecation window
# ---------------------------------------------------------------------------

def test_legacy_shims_removed():
    """The PR-5 ``EdgeSystem.query/query_batched/query_many`` shims are
    gone — the service front door is the only query entry point (the
    scalar reference stays as ``query_loop``)."""
    for name in ("query", "query_batched", "query_many",
                 "_query_batched_via_service"):
        assert not hasattr(EdgeSystem, name), name
    assert hasattr(EdgeSystem, "query_loop")
    assert hasattr(EdgeSystem, "service")


# ---------------------------------------------------------------------------
# rebuild-window policies mid traffic update (apply_traffic_update path)
# ---------------------------------------------------------------------------

def test_policy_modes_mid_apply_traffic_update(system):
    """The acceptance scenario: a LIVE apply_traffic_update rebuild
    window (locals refreshed, B rebuilt, push pending), served under all
    three policies — certified answers identical, install_now equals the
    certify_or_wait distances everywhere and closes the window."""
    g, part, _ = system
    sys_ = EdgeSystem.deploy(g, part)
    rng = np.random.default_rng(12)
    w2 = perturb_weights(g, rng, lo=0.7, hi=1.4)
    g2 = sys_.graph.with_weights(w2)
    sys_.graph = g2
    for srv in sys_.servers:          # local refresh half of the cycle
        srv.refresh_local(g2, part)
    sys_.center.rebuild(w2)           # BL rebuilt; push still pending
    assert sys_.current_engine() is None
    ss, ts = _batch(g, rng, size=192)
    stale_b = sys_.service(ServingPolicy(rebuild=STALE_OK)).submit(ss, ts)
    wait_b = sys_.service(ServingPolicy(rebuild=CERTIFY_OR_WAIT)).submit(
        ss, ts)
    assert sys_.current_engine() is None      # still side-effect free
    now_b = sys_.service().submit(ss, ts)     # install_now default
    certified = stale_b.exactness_codes == 1
    assert certified.any() and (~stale_b.exact).any()
    np.testing.assert_array_equal(stale_b.distances[certified],
                                  now_b.distances[certified])
    np.testing.assert_array_equal(wait_b.distances, now_b.distances)
    # install_now closed the window; steady state now serves identically
    assert sys_.current_engine() is not None
    np.testing.assert_array_equal(sys_.service().submit(ss, ts).distances,
                                  now_b.distances)


# ---------------------------------------------------------------------------
# 8-virtual-device mesh
# ---------------------------------------------------------------------------

def _mesh8_case():
    """Runs on however many devices the backend exposes: service parity
    across placements + policy modes mid-window (imported by the
    subprocess runner below and exercised in-process by tier1-mesh8)."""
    from repro.serve import STALE_OK, ServingPolicy

    g = grid_road_network(10, 10, seed=5)
    part = bfs_grow_partition(g, 8, seed=1)
    sys_ = EdgeSystem.deploy(g, part)
    rng = np.random.default_rng(3)
    ss = rng.integers(0, g.num_vertices, size=384)
    ts = rng.integers(0, g.num_vertices, size=384)
    loop = sys_.query_loop(ss, ts)
    for pol in (ServingPolicy(), ServingPolicy(engine="replicated"),
                ServingPolicy(engine="sharded", shard_border=True)):
        np.testing.assert_array_equal(
            sys_.service(pol).submit(ss, ts).distances, loop)
    w2 = perturb_weights(g, np.random.default_rng(5), lo=0.8, hi=1.3)
    g2 = sys_.graph.with_weights(w2)
    sys_.graph = g2
    for srv in sys_.servers:
        srv.refresh_local(g2, part)
    sys_.center.rebuild(w2)
    stale_b = sys_.service(ServingPolicy(rebuild=STALE_OK)).submit(ss, ts)
    now_b = sys_.service().submit(ss, ts)
    certified = stale_b.exactness_codes == 1
    np.testing.assert_array_equal(stale_b.distances[certified],
                                  now_b.distances[certified])
    return True


def test_service_mesh_case_in_process():
    assert _mesh8_case()


@pytest.mark.slow
def test_service_eight_virtual_devices():
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=8")
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src")]
        + env.get("PYTHONPATH", "").split(os.pathsep))
    out = subprocess.run(
        [sys.executable, "-c",
         "import jax; assert len(jax.devices()) == 8;"
         "import tests.test_service as m; assert m._mesh8_case();"
         "print('OK8')"],
        env=env, capture_output=True, text=True, timeout=500,
        cwd=os.path.join(os.path.dirname(__file__), ".."))
    assert out.returncode == 0, out.stderr[-2000:]
    assert "OK8" in out.stdout
