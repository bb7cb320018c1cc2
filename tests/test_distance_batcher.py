"""DistanceBatcher / BatchedDecoder edge cases: empty queue, groups
smaller than batch_size, and rid=-1 padding never leaking into completed
requests or latency statistics."""
from collections import deque

import jax
import numpy as np

from repro.configs.base import get_smoke_config
from repro.core import bfs_grow_partition, grid_road_network
from repro.edge import EdgeSystem
from repro.models.lm import init_params
from repro.serve import (BatchedDecoder, DistanceBatcher, DistanceRequest,
                         Request)


def _echo_engine(calls):
    def engine(ss, ts):
        calls.append((len(ss), len(ts)))
        return (ss * 10 + ts).astype(np.float32)
    return engine


def test_distance_batcher_empty_queue():
    calls = []
    b = DistanceBatcher(_echo_engine(calls), batch_size=4)
    assert b.run() == []
    assert calls == []
    assert b.latency_stats()["count"] == 0


def test_distance_batcher_group_smaller_than_batch():
    calls = []
    b = DistanceBatcher(_echo_engine(calls), batch_size=8)
    b.submit_pairs([(1, 2), (3, 4), (5, 6)])
    done = b.run()
    # the engine only ever sees the static batch shape
    assert calls == [(8, 8)]
    assert [r.rid for r in done] == [0, 1, 2]
    assert [r.distance for r in done] == [12.0, 34.0, 56.0]


def test_distance_batcher_padding_never_leaks():
    calls = []
    b = DistanceBatcher(_echo_engine(calls), batch_size=4)
    b.submit_pairs([(i, i + 1) for i in range(10)])
    done = b.run()
    assert calls == [(4, 4)] * 3                 # 10 requests → 3 groups
    assert sorted(r.rid for r in done) == list(range(10))
    assert all(r.rid >= 0 for r in b.completed)
    st = b.latency_stats()
    assert st["count"] == 10
    assert st["p95_ms"] >= st["p50_ms"] >= 0.0
    for r in done:
        assert r.finished_s is not None and r.latency_s > 0


def test_distance_batcher_pad_false_sends_short_tail():
    calls = []
    b = DistanceBatcher(_echo_engine(calls), batch_size=4, pad=False)
    b.submit_pairs([(i, i) for i in range(6)])
    done = b.run()
    assert calls == [(4, 4), (2, 2)]            # tail not padded
    assert sorted(r.rid for r in done) == list(range(6))
    assert b.latency_stats()["count"] == 6


def test_distance_batcher_padding_invisible_mid_run():
    """latency_stats / completed observed from inside an engine call
    (i.e. mid-run) must never see rid=-1 padding dummies."""
    b = DistanceBatcher(lambda ss, ts: None, batch_size=4)
    mid = []

    def engine(ss, ts):
        mid.append((b.latency_stats()["count"],
                    [r.rid for r in b.completed]))
        return np.zeros(len(ss), dtype=np.float32)

    b.engine = engine
    b.submit_pairs([(i, i) for i in range(6)])   # groups: 4 real, 2+2 pad
    b.run()
    assert mid == [(0, []), (4, [0, 1, 2, 3])]
    assert [r.rid for r in b.completed] == list(range(6))


def test_distance_batcher_engine_object_plug_in():
    """Engine objects exposing .query / .query_batched plug in directly."""
    class _Eng:
        def query(self, ss, ts):
            return (ss + ts).astype(np.float32)

    b = DistanceBatcher(_Eng(), batch_size=2, pad=False)
    b.submit_pairs([(1, 2), (3, 4)])
    assert [r.distance for r in b.run()] == [3.0, 7.0]


def test_distance_batcher_drain_is_linear():
    """The queue drains via deque.popleft — O(n) overall, and a large
    drain leaves the queue empty with all requests completed in order."""
    calls = []
    b = DistanceBatcher(_echo_engine(calls), batch_size=64, pad=False)
    # the O(n) guarantee comes from deque.popleft — a plain list would
    # pass the behavioral asserts below while reintroducing O(n²) shifts
    assert isinstance(b.queue, deque)
    b.submit_pairs([(i % 7, i % 5) for i in range(5000)])
    done = b.run()
    assert len(done) == 5000 and len(b.queue) == 0
    assert [r.rid for r in done] == list(range(5000))


def test_distance_batcher_requeue_after_drain():
    calls = []
    b = DistanceBatcher(_echo_engine(calls), batch_size=2)
    b.submit(DistanceRequest(rid=0, s=1, t=1))
    assert len(b.run()) == 1
    b.submit(DistanceRequest(rid=1, s=2, t=2))
    done = b.run()
    assert sorted(r.rid for r in done) == [0, 1]
    assert all(r.rid >= 0 for r in b.completed)


def test_distance_batcher_latency_stats_padded_tail():
    """Percentiles (incl. the new p999) are computed over REAL requests
    only: a heavily-padded tail group (1 real + 7 dummies) must not
    deflate the stats, and the shed counter starts at zero."""
    calls = []
    b = DistanceBatcher(_echo_engine(calls), batch_size=8)
    b.submit_pairs([(i, i + 1) for i in range(9)])    # 8 full + 1-real tail
    done = b.run()
    assert calls == [(8, 8), (8, 8)]
    assert len(done) == 9
    st = b.latency_stats()
    assert st["count"] == 9 and st["shed"] == 0
    assert {"p50_ms", "p95_ms", "p99_ms", "p999_ms"} <= st.keys()
    assert st["p999_ms"] >= st["p99_ms"] >= st["p95_ms"] >= st["p50_ms"] > 0
    # empty-stats shape carries the same keys (report code indexes them)
    empty = DistanceBatcher(_echo_engine([]), batch_size=4).latency_stats()
    assert empty["count"] == 0 and empty["p999_ms"] == 0.0
    assert empty.keys() == st.keys()


def test_distance_batcher_bounded_queue_sheds():
    """max_queue bounds admission: overflow submits are dropped (False),
    counted in shed_count / latency_stats()["shed"], and never answered;
    draining frees capacity for later admissions."""
    calls = []
    b = DistanceBatcher(_echo_engine(calls), batch_size=4, max_queue=4)
    admitted = b.submit_pairs([(i, i) for i in range(10)])
    assert admitted == 4 and b.shed_count == 6
    assert len(b.queue) == 4
    done = b.run()
    assert sorted(r.rid for r in done) == [0, 1, 2, 3]
    st = b.latency_stats()
    assert st["count"] == 4 and st["shed"] == 6
    # queue drained → admission reopens
    assert b.submit(DistanceRequest(rid=10, s=0, t=0)) is True
    assert b.shed_count == 6


def test_distance_batcher_max_queue_validation():
    import pytest
    with pytest.raises(ValueError, match="max_queue"):
        DistanceBatcher(_echo_engine([]), batch_size=4, max_queue=0)


def test_distance_batcher_max_queue_boundary():
    """max_queue=1 — the tightest legal bound: admission closes at
    exactly the bound (not one past it) and reopens per drain."""
    calls = []
    b = DistanceBatcher(_echo_engine(calls), batch_size=2, max_queue=1)
    assert b.submit(DistanceRequest(rid=0, s=1, t=2)) is True
    assert b.submit(DistanceRequest(rid=1, s=3, t=4)) is False
    assert len(b.queue) == 1 and b.shed_count == 1
    assert [r.rid for r in b.run()] == [0]
    assert b.submit(DistanceRequest(rid=2, s=5, t=6)) is True
    assert [r.rid for r in b.run()] == [0, 2]
    assert b.latency_stats()["shed"] == 1
    # boundary at max_queue == batch_size: a full group admits exactly
    b2 = DistanceBatcher(_echo_engine([]), batch_size=4, max_queue=4)
    assert b2.submit_pairs([(i, i) for i in range(5)]) == 4


def test_distance_batcher_rerun_after_drain_is_noop():
    """A second run() on the drained queue must not call the engine
    again (empty-batch drain) and returns the same completed list."""
    calls = []
    b = DistanceBatcher(_echo_engine(calls), batch_size=4)
    b.submit_pairs([(1, 2), (3, 4)])
    done = b.run()
    n_calls = len(calls)
    assert b.run() == done and len(calls) == n_calls


def test_distance_batcher_padding_under_shedding_service_path():
    """Shed + pad through a DistanceService: a bounded queue drains as a
    padded group whose rid=-1 dummies are masked out of the rule
    counters — counters see exactly the admitted reals."""
    g = grid_road_network(6, 6, seed=3)
    part = bfs_grow_partition(g, 2, seed=1)
    svc = EdgeSystem.deploy(g, part).service()
    b = DistanceBatcher(svc, batch_size=8, max_queue=3)
    admitted = b.submit_pairs([(i, (i * 7 + 3) % g.num_vertices)
                               for i in range(9)])
    assert admitted == 3 and b.shed_count == 6
    done = b.run()
    assert sorted(r.rid for r in done) == [0, 1, 2]
    assert sum(svc.stats[k] for k in ("rule1", "rule2", "rule3")) == 3
    loop = svc.system.query_loop(np.array([r.s for r in done]),
                                 np.array([r.t for r in done]))
    np.testing.assert_array_equal(
        np.array([r.distance for r in done], dtype=np.float32), loop)


def test_distance_batcher_all_padding_mask_skips_counters():
    """The warmup shape: service.submit with real=all-False computes
    distances but bumps no counters (how OpenLoopLoadGen warms the
    engine without polluting stats)."""
    g = grid_road_network(6, 6, seed=3)
    part = bfs_grow_partition(g, 2, seed=1)
    svc = EdgeSystem.deploy(g, part).service()
    zeros = np.zeros(8, dtype=np.int64)
    out = svc.submit(zeros, zeros, real=np.zeros(8, dtype=bool))
    np.testing.assert_array_equal(out.distances, np.zeros(8, np.float32))
    assert sum(svc.stats[k] for k in ("rule1", "rule2", "rule3")) == 0


def test_decoder_empty_queue_and_padding():
    cfg = get_smoke_config("qwen3_4b").reduced(num_layers=2)
    params = init_params(cfg, jax.random.PRNGKey(0))
    dec = BatchedDecoder(cfg, params, batch_size=4, max_len=16)
    assert dec.run() == []                       # empty queue is a no-op
    dec.submit(Request(rid=0, prompt=[1, 2], max_new_tokens=2))
    dec.submit(Request(rid=1, prompt=[3], max_new_tokens=3))
    done = dec.run()                             # group of 2 + 2 dummies
    assert sorted(r.rid for r in done) == [0, 1]
    assert all(r.rid >= 0 for r in dec.completed)
    for r in done:
        assert len(r.tokens) == r.max_new_tokens
        assert r.latency_s > 0
