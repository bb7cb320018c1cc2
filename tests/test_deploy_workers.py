"""``EdgeSystem.deploy`` building the districts' edge servers in worker
processes: the same servers, bit for bit, as one process builds."""
import os
from types import SimpleNamespace

import numpy as np
import pytest

from repro import obs
from repro.core import bfs_grow_partition, grid_road_network
from repro.edge import EdgeSystem, router
from repro.edge.router import (DEPLOY_POOL_MIN_VERTICES, deploy_workers,
                               usable_cpus)


@pytest.fixture(autouse=True)
def clean():
    obs.disable()
    obs.reset()
    yield
    obs.disable()
    obs.reset()


def _same_index(a, b):
    assert a.augmented == b.augmented and a.district_id == b.district_id
    for name in ("vertices", "border_locals"):
        assert np.array_equal(getattr(a, name), getattr(b, name))
    assert np.array_equal(a.labels.hubs, b.labels.hubs)
    # bit for bit, infinities included
    for x, y in ((a.labels.dists, b.labels.dists),
                 (a.border_dist, b.border_dist)):
        assert x.dtype == y.dtype == np.float32
        assert np.array_equal(x.view(np.uint32), y.view(np.uint32))


def test_workers_build_the_same_servers_as_one_process(monkeypatch):
    g = grid_road_network(12, 12, seed=5)
    part = bfs_grow_partition(g, 6, seed=1)
    assert deploy_workers(g, part) == 1         # small: in this process
    one = EdgeSystem.deploy(g, part)
    monkeypatch.setattr(router, "deploy_workers", lambda g, part: 2)
    obs.enable()
    two = EdgeSystem.deploy(g, part)
    spans = obs.drain()
    assert len(two.servers) == part.num_districts
    for a, b in zip(one.servers, two.servers):
        assert a.district_id == b.district_id
        assert a.augmented_version == b.augmented_version == \
            two.center.version
        _same_index(a.plain, b.plain)
        _same_index(a.augmented, b.augmented)
    # one server span over the pool instead of one per district
    assert [s.name for s in spans] == ["repro.deploy.center",
                                       "repro.deploy.server"]
    assert spans[1].attrs == {"districts": part.num_districts,
                              "workers": 2}
    rng = np.random.default_rng(3)
    s, t = (rng.integers(0, g.num_vertices, 300) for _ in range(2))
    a = one.service().submit(s, t).distances
    b = two.service().submit(s, t).distances
    assert np.array_equal(a, b)


def test_the_auto_pick_uses_a_pool_only_for_large_networks():
    part = SimpleNamespace(num_districts=64)
    small = SimpleNamespace(num_vertices=DEPLOY_POOL_MIN_VERTICES - 1)
    assert deploy_workers(small, part) == 1
    large = SimpleNamespace(num_vertices=DEPLOY_POOL_MIN_VERTICES)
    workers = deploy_workers(large, part)
    assert 1 <= workers <= 64
    assert deploy_workers(large, SimpleNamespace(num_districts=1)) == 1


def test_usable_cpus_is_within_what_the_os_reports():
    assert 1 <= usable_cpus() <= (os.cpu_count() or 1)
