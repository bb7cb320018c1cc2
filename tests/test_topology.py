"""§4.1 latency-model contract: every ``Topology`` RTT helper pinned
against hand-computed values, on the default and non-default
``LatencyModel``s, plus the loadgen's per-request RTT routing
(``repro.serve.request_rtt_ms``) that all harness RTT math flows
through."""
import numpy as np
import pytest

from repro.edge import LatencyModel, Topology
from repro.serve import request_rtt_ms


def test_default_latency_model_rtts():
    topo = Topology(num_districts=8)
    lm = topo.latency
    assert (lm.client_edge_ms, lm.edge_center_ms,
            lm.client_center_ms) == (5.0, 30.0, 35.0)
    # hand-computed round trips from the §4.1 hop structure
    assert topo.edge_rtt_ms() == 10.0          # 2 · 5
    assert topo.center_rtt_ms() == 70.0        # 2 · (5 + 30)
    assert topo.forward_rtt_ms() == 130.0      # 2 · (5 + 2·30): two WAN hops
    assert topo.centralized_rtt_ms() == 70.0   # 2 · 35
    assert topo.edge_rtt_ms() < topo.center_rtt_ms() < topo.forward_rtt_ms()


@pytest.mark.parametrize("ce,ec,cc", [(2.0, 11.0, 13.0),
                                      (0.5, 40.0, 41.0)])
def test_custom_latency_model_rtts(ce, ec, cc):
    lm = LatencyModel(client_edge_ms=ce, edge_center_ms=ec,
                      client_center_ms=cc)
    topo = Topology(4, lm)
    assert topo.edge_rtt_ms() == 2 * ce
    assert topo.center_rtt_ms() == 2 * (ce + ec)
    assert topo.forward_rtt_ms() == 2 * (ce + 2 * ec)
    assert topo.centralized_rtt_ms() == 2 * cc


def test_request_rtt_routes_through_topology_helpers():
    """Same-district lanes pay the edge RTT; cross lanes pay the
    forwarded (two-WAN-hop) RTT — NOT the center RTT the old inline
    constants charged."""
    topo = Topology(num_districts=8)
    cross = np.array([False, True, True, False])
    np.testing.assert_array_equal(
        request_rtt_ms(topo, cross),
        np.array([10.0, 130.0, 130.0, 10.0]))
    # regression: the forwarded path is 2·(5 + 2·30), not 2·(5 + 30)
    assert request_rtt_ms(topo, np.array([True]))[0] != topo.center_rtt_ms()


def test_request_rtt_custom_model():
    lm = LatencyModel(client_edge_ms=1.0, edge_center_ms=10.0,
                      client_center_ms=11.0)
    topo = Topology(2, lm)
    cross = np.array([True, False])
    np.testing.assert_array_equal(request_rtt_ms(topo, cross),
                                  np.array([42.0, 2.0]))
