"""The benchmark's own generator, traffic and reference, tied to the
program at a tiny size (CPU)."""
from __future__ import annotations

import json
import os
import subprocess
import sys

import numpy as np
import pytest

HARNESS = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(HARNESS))
sys.path.insert(0, HARNESS)
sys.path.insert(0, os.path.join(ROOT, "src"))

from chipbench import network, reference, traffic  # noqa: E402
from chipbench.bench import make_network, to_program  # noqa: E402


def _edges(u, v, w):
    lo, hi = np.minimum(u, v), np.maximum(u, v)
    order = np.lexsort((hi, lo))
    return np.stack([lo[order], hi[order], w[order]], axis=1)


@pytest.mark.parametrize("grid,district,links,seed", [
    ((2, 2), (16, 16), 2, 0),
    ((3, 2), (8, 16), 8, 7),
    ((8, 8), (16, 16), 2, 2**33 + 1),
])
def test_generator_gives_the_programs_edges(grid, district, links, seed):
    from repro.ingest import synthetic_continent
    csr, part = synthetic_continent(grid=grid, district=district,
                                    border_links=links, seed=seed)
    g = csr.to_graph()
    src = np.repeat(np.arange(g.num_vertices), np.diff(g.indptr))
    keep = src < g.indices
    want = _edges(src[keep], g.indices[keep].astype(np.int64),
                  g.weights[keep].astype(np.int64))
    net = network.continent(grid, district, links, 15,
                            np.random.default_rng(seed))
    np.testing.assert_array_equal(_edges(net.u, net.v, net.w), want)
    np.testing.assert_array_equal(net.district, part.assignment)


def test_program_graph_from_the_harness_network():
    net = network.continent((2, 2), (8, 8), 2, 15, np.random.default_rng(3))
    g, part = to_program(net)
    assert g.num_vertices == net.num_vertices
    assert g.num_edges == len(net.u)
    assert part.num_districts == 4


def test_reference_equals_bidirectional_dijkstra():
    from repro.core import bidirectional_dijkstra
    net = network.continent((2, 2), (16, 16), 2, 15,
                            np.random.default_rng(5))
    g, _ = to_program(net)
    ref = reference.Reference(net)
    rng = np.random.default_rng(0)
    for s in rng.integers(0, net.num_vertices, 6):
        dist = ref.distances_from(int(s))
        for t in rng.integers(0, net.num_vertices, 8):
            assert dist[t] == bidirectional_dijkstra(g, int(s), int(t))


CONFIG = {"grid": [4, 3], "district": [8, 8], "border_links": 2,
          "weight_high": 15}
TRIPS = {"driver": "closed_loop", "pool": 8,
         "pairs": {"kind": "trips", "per_request": 64, "origin_zipf": 1.0,
                   "same_district": 0.6, "hop_decay": 0.5}}
MATRIX = {"driver": "closed_loop", "pool": 8,
          "pairs": {"kind": "matrix", "sources": 16, "targets": 32}}


@pytest.mark.parametrize("seed", [1, 2**31 + 11])
def test_network_is_reproducible_from_the_seed(seed):
    a, b = make_network(CONFIG, seed), make_network(CONFIG, seed)
    np.testing.assert_array_equal(a.w, b.w)
    np.testing.assert_array_equal(a.u, b.u)
    assert not np.array_equal(make_network(CONFIG, seed + 1).w, a.w)


def test_trip_mix_keeps_its_shares():
    net = make_network(CONFIG, 1)
    tr = traffic.generate(dict(TRIPS, pool=1600), net,
                          np.random.default_rng(4))
    tr.ss, tr.ts = tr.ss.ravel(), tr.ts.ravel()
    same = net.district[tr.ss] == net.district[tr.ts]
    assert abs(same.mean() - 0.6) < 0.01
    xy = net.district_xy()
    hops = np.abs(xy[net.district[tr.ss]] - xy[net.district[tr.ts]]).sum(1)
    away = hops[~same]
    # weight 0.5 ** hops: neighbours are asked far more than the far side
    assert (away == 1).mean() > (away == 3).mean() > 0
    origins = np.bincount(net.district[tr.ss], minlength=12)
    assert origins.max() > 3 * np.median(origins)     # Zipf skew


@pytest.mark.parametrize("seed", [3, 2**32 + 3])
def test_matrix_pool_is_reproducible(seed):
    net = make_network(CONFIG, 1)
    a = traffic.generate(MATRIX, net, np.random.default_rng(seed))
    b = traffic.generate(MATRIX, net, np.random.default_rng(seed))
    np.testing.assert_array_equal(a.sources, b.sources)
    ss, ts = a.request(9)
    assert len(ss) == 16 * 32
    np.testing.assert_array_equal(ss[:32], np.full(32, a.sources[1, 0]))
    np.testing.assert_array_equal(ts[:32], a.targets[1])
    rs, rt = a.row_pairs(9, 3)
    np.testing.assert_array_equal(rs, ss[3 * 32:4 * 32])
    np.testing.assert_array_equal(rt, ts[3 * 32:4 * 32])


@pytest.mark.parametrize("seed", [1, 2**31 + 11])
def test_trip_pool_is_reproducible(seed):
    net = make_network(CONFIG, 1)
    a = traffic.generate(TRIPS, net, np.random.default_rng(seed))
    b = traffic.generate(TRIPS, net, np.random.default_rng(seed))
    c = traffic.generate(TRIPS, net, np.random.default_rng(seed + 1))
    np.testing.assert_array_equal(a.ss, b.ss)
    np.testing.assert_array_equal(a.ts, b.ts)
    # the seed changes which trips, never how many
    assert a.ss.shape == c.ss.shape == (8, 64)
    assert not np.array_equal(a.ss, c.ss)
    ss, ts = a.request(9)
    assert (a.rows, a.width) == (1, 64)
    np.testing.assert_array_equal(ss, a.ss[1])
    np.testing.assert_array_equal(ts, a.ts[1])


def test_run_refuses_to_run_off_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(HARNESS, "run.py"),
         "--workload", "sparse-trips", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True,
        text=True, timeout=120)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    for line in proc.stdout.splitlines():
        with pytest.raises(json.JSONDecodeError):
            json.loads(line)
