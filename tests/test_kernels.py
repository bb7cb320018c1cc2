"""Per-kernel shape/dtype sweeps: Pallas (interpret mode) vs pure-jnp ref."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.label_join import ops as lj
from repro.kernels.label_join.kernel import (WHOLE_WIDTH_VMEM_BYTES,
                                             join_lb_pallas, join_pallas)
from repro.kernels.label_join.ref import (join_ref, join_sparse_ref,
                                          local_bound_ref)
from repro.kernels.minplus.kernel import minplus_pallas, relax_pallas
from repro.kernels.minplus.ops import bellman_ford, closure
from repro.kernels.minplus.ref import minplus_ref, relax_ref
from repro.kernels.sssp_relax.kernel import floyd_warshall_pallas
from repro.kernels.sssp_relax.ref import floyd_warshall_ref, multi_source_ref

jax.config.update("jax_enable_x64", False)


def _rand_dist(rng, shape, inf_frac=0.3):
    x = rng.uniform(0.5, 50.0, size=shape).astype(np.float32)
    mask = rng.random(shape) < inf_frac
    x[mask] = np.inf
    return jnp.asarray(x)


MINPLUS_SHAPES = [
    (8, 8, 8), (16, 32, 8), (128, 128, 128), (130, 70, 33),
    (256, 128, 64), (1, 128, 1), (37, 1, 53),
]


@pytest.mark.parametrize("m,k,n", MINPLUS_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_minplus_matches_ref(m, k, n, dtype):
    rng = np.random.default_rng(m * 1000 + k * 10 + n)
    a = _rand_dist(rng, (m, k)).astype(dtype)
    b = _rand_dist(rng, (k, n)).astype(dtype)
    got = minplus_pallas(a, b, bm=32, bn=32, bk=32, interpret=True)
    ref = minplus_ref(a.astype(jnp.float32), b.astype(jnp.float32))
    np.testing.assert_allclose(np.asarray(got, dtype=np.float32),
                               np.asarray(ref), rtol=2e-2, atol=1e-2)


@pytest.mark.parametrize("s,v", [(4, 16), (16, 64), (33, 130), (128, 128)])
def test_relax_matches_ref(s, v):
    rng = np.random.default_rng(s * 100 + v)
    d = _rand_dist(rng, (s, v))
    a = _rand_dist(rng, (v, v), inf_frac=0.6)
    got = relax_pallas(d, a, bm=32, bn=32, bk=32, interpret=True)
    ref = relax_ref(d, a)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=1e-6)


def test_bellman_ford_converges_to_dijkstra():
    from repro.core import grid_road_network, dijkstra
    g = grid_road_network(6, 6, seed=3)
    adj = jnp.asarray(g.dense_adjacency())
    n = g.num_vertices
    init = jnp.full((3, n), jnp.inf).at[[0, 1, 2], [0, 5, 17]].set(0.0)
    out = bellman_ford(init, adj, iters=n)
    for row, src in zip(np.asarray(out), [0, 5, 17]):
        np.testing.assert_allclose(row, dijkstra(g, src), rtol=1e-5)


def test_closure_matches_numpy_closure():
    from repro.core import minplus_closure
    rng = np.random.default_rng(7)
    w = np.asarray(_rand_dist(rng, (40, 40), inf_frac=0.7))
    got = np.asarray(closure(jnp.asarray(w)))
    ref = minplus_closure(w)
    np.testing.assert_allclose(got, ref, rtol=1e-5)


# (40, 9000): wider than the whole-width block at bq=32, so the hub axis
# is tiled by bh=64 and the tail lanes are masked
JOIN_SHAPES = [(1, 1), (5, 7), (64, 128), (100, 257), (512, 512), (3, 1024),
               (40, 9000)]


@pytest.mark.parametrize("q,h", JOIN_SHAPES)
def test_join_matches_ref(q, h):
    rng = np.random.default_rng(q * 31 + h)
    s = _rand_dist(rng, (q, h))
    t = _rand_dist(rng, (q, h))
    got = join_pallas(s, t, bq=32, bh=64, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(join_ref(s, t)),
                               rtol=1e-6)


def _codes(rng, shape, dtype):
    """Label codes below the sentinel, sentinel lanes in every row, and
    a few rows that are all sentinel."""
    sent = int(np.iinfo(dtype).max)
    c = rng.integers(0, sent, size=shape).astype(dtype)
    c[rng.random(shape) < 0.3] = sent
    c[rng.integers(0, shape[0], size=3)] = sent
    return c, sent


# 2166: the 17x17 deployment's width, past the whole-width block of
# uint16/int16 rows at bq=256, so the hub axis is tiled and masked
JOIN_CODE_WIDTHS = [1, 130, 446, 928, 2166]


@pytest.mark.parametrize("qn", [256, 300], ids=["rows_whole", "rows_pad"])
@pytest.mark.parametrize("width", JOIN_CODE_WIDTHS)
@pytest.mark.parametrize("dtype", [np.uint16, np.int16])
def test_join_codes_bitwise_match_xla_and_float(dtype, width, qn):
    """The kernel's in-VMEM widening of stored codes gives, bit for bit,
    the XLA int32 accumulate and the float32 join of the dequantized
    rows."""
    rng = np.random.default_rng(width * 7 + qn)
    s, sent = _codes(rng, (qn, width), dtype)
    t, _ = _codes(rng, (qn, width), dtype)
    if width == 2166:
        assert 4 * 256 * width * s.itemsize > WHOLE_WIDTH_VMEM_BYTES
    kernel = np.asarray(join_pallas(jnp.asarray(s), jnp.asarray(t),
                                    sentinel=sent, interpret=True))
    assert kernel.dtype == np.float32
    xla = np.asarray(lj.join_quantized(jnp.asarray(s), jnp.asarray(t),
                                       sentinel=sent, scale=1.0,
                                       use_pallas=False))
    pallas = np.asarray(lj.join_quantized(jnp.asarray(s), jnp.asarray(t),
                                          sentinel=sent, scale=1.0,
                                          use_pallas=True))

    def dequant(c):
        return np.where(c == sent, np.inf, c.astype(np.float32))
    f32 = np.asarray(join_ref(jnp.asarray(dequant(s)),
                              jnp.asarray(dequant(t))))
    assert np.isposinf(f32).any() and np.isfinite(f32).any()
    for got in (kernel, pallas, f32):
        np.testing.assert_array_equal(got, xla)


@pytest.mark.parametrize("q,h", [(16, 32), (100, 130), (257, 64)])
def test_join_lb_fused_matches_refs(q, h):
    rng = np.random.default_rng(q + h)
    s = _rand_dist(rng, (q, h))
    t = _rand_dist(rng, (q, h))
    lam, lb = join_lb_pallas(s, t, bq=32, bh=64, interpret=True)
    np.testing.assert_allclose(np.asarray(lam), np.asarray(join_ref(s, t)),
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(lb),
                               np.asarray(local_bound_ref(s, t)), rtol=1e-6)


def test_join_sparse_ref_matches_core_labels():
    from repro.core import grid_road_network, pll
    g = grid_road_network(5, 5, seed=2)
    labels = pll(g)
    rng = np.random.default_rng(3)
    ss = rng.integers(0, g.num_vertices, size=30)
    ts = rng.integers(0, g.num_vertices, size=30)
    got = np.asarray(join_sparse_ref(
        jnp.asarray(labels.hubs[ss]), jnp.asarray(labels.dists[ss]),
        jnp.asarray(labels.hubs[ts]), jnp.asarray(labels.dists[ts])))
    ref = labels.query_many(ss, ts)
    np.testing.assert_allclose(got, ref, rtol=1e-6)


FW_SIZES = [8, 32, 33, 64, 100, 130]


@pytest.mark.parametrize("n", FW_SIZES)
def test_floyd_warshall_matches_ref(n):
    rng = np.random.default_rng(n)
    adj = np.asarray(_rand_dist(rng, (n, n), inf_frac=0.8))
    adj = np.minimum(adj, adj.T)  # undirected
    got = floyd_warshall_pallas(jnp.asarray(adj), bk=32, interpret=True)
    ref = floyd_warshall_ref(jnp.asarray(adj))
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=1e-5)


def test_floyd_warshall_against_dijkstra():
    from repro.core import grid_road_network, dijkstra
    g = grid_road_network(6, 5, seed=4)
    adj = jnp.asarray(g.dense_adjacency())
    got = np.asarray(floyd_warshall_pallas(adj, bk=16, interpret=True))
    for src in (0, 7, 29):
        np.testing.assert_allclose(got[src], dijkstra(g, src), rtol=1e-5)


def test_multi_source_ref_matches_bf():
    rng = np.random.default_rng(11)
    adj = np.asarray(_rand_dist(rng, (30, 30), inf_frac=0.7))
    adj = np.minimum(adj, adj.T)
    np.fill_diagonal(adj, 0.0)
    init = np.full((2, 30), np.inf, dtype=np.float32)
    init[0, 0] = 0.0
    init[1, 9] = 0.0
    out = multi_source_ref(jnp.asarray(adj), jnp.asarray(init), iters=30)
    fw = floyd_warshall_ref(jnp.asarray(adj))
    np.testing.assert_allclose(np.asarray(out)[0], np.asarray(fw)[0],
                               rtol=1e-5)
    np.testing.assert_allclose(np.asarray(out)[1], np.asarray(fw)[9],
                               rtol=1e-5)
