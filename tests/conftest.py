"""Shared session-scoped fixtures.

``test_service`` / ``test_border_sharding`` each used to build the
SAME (10×10, 8-district) graph and deploy an ``EdgeSystem`` over it —
two deploys of identical state per tier-1 run; ``test_query_engine`` did the same with the smaller (8×8,
4-district) case.  These fixtures build each once per session.

The deployed systems are READ-ONLY: every test that mutates serving
state (traffic updates, rebuild windows, shortcut installs) deploys its
own system inside the test body — that audit is what makes session
scope safe, including under ``pytest -p randomly`` order shuffling.
Keep it that way: if a new test needs to mutate, deploy fresh.
"""
import pytest

from repro.core import bfs_grow_partition, grid_road_network
from repro.edge import EdgeSystem

# -- mesh8 case: 10×10 grid, 8 districts (the tier1-mesh8 workload) ----------


@pytest.fixture(scope="session")
def mesh8_graph():
    g = grid_road_network(10, 10, seed=5)
    part = bfs_grow_partition(g, 8, seed=1)
    return g, part


@pytest.fixture(scope="session")
def mesh8_system(mesh8_graph):
    g, part = mesh8_graph
    return g, part, EdgeSystem.deploy(g, part)


# -- small case: 8×8 grid, 4 districts ---------------------------------------


@pytest.fixture(scope="session")
def small_graph():
    g = grid_road_network(8, 8, seed=11)
    part = bfs_grow_partition(g, 4, seed=0)
    return g, part


@pytest.fixture(scope="session")
def small_system(small_graph):
    g, part = small_graph
    return g, part, EdgeSystem.deploy(g, part)
