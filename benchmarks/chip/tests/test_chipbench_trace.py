"""Trace reduction, peaks and the byte count of the roofline share
(CPU)."""
from __future__ import annotations

import importlib.util
import os
import sys

import numpy as np
import pytest

HARNESS = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HARNESS)

from chipbench import peaks, trace  # noqa: E402
from chipbench.trace import Event  # noqa: E402

DATA = os.path.join(HARNESS, "tests", "data")
TPU0, TPU1, HOST = "/device:TPU:0", "/device:TPU:1", "/host:CPU"


def _roofline():
    path = os.path.join(HARNESS, "metrics", "step_hbm_roofline.matrix.py")
    spec = importlib.util.spec_from_file_location("roofline_metric", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _op(plane, start, dur, name="fusion"):
    return Event(plane, trace.OPS_LINE, name, start, dur)


def test_busy_union_idle_share_and_gaps_on_a_hand_made_list():
    events = [
        Event(HOST, "python", trace.STRETCH, 1000, 10000),
        Event(HOST, "python", "bench.submit", 1000, 4000),
        Event(HOST, "python", "bench.wait", 5000, 6000),
        # chip 0: two overlapping ops and one that sticks out of the
        # stretch on the right: busy [2000, 4000] + [9000, 11000]
        _op(TPU0, 2000, 1500, "gather"),
        _op(TPU0, 3000, 1000, "join"),
        _op(TPU0, 9000, 4000, "join"),
        # chip 1: busy [0, 2000] clipped to [1000, 2000]
        _op(TPU1, 0, 2000, "gather"),
        Event(TPU0, trace.MODULES_LINE, "jit__engine_fn_quantized(7)",
              2000, 2000),
        Event(TPU0, trace.MODULES_LINE, "jit_other(1)", 9000, 500),
        # lines the reduction ignores
        Event(TPU0, "Steps", "step", 0, 20000),
        Event(HOST, "python", "PjitFunction", 1000, 9000),
    ]
    s = trace.reduce(events)
    assert s.window_s == pytest.approx(10000e-9)
    assert s.chips == 2
    # (4000 + 1000) ns busy over two chips
    assert s.busy_s == pytest.approx(2500e-9)
    assert s.idle_share == pytest.approx(0.75)
    assert s.program_time(["jit__engine_fn_quantized"]) == \
        pytest.approx((2000e-9, 1))
    assert s.program_time(["jit__engine_fn"]) == (0.0, 0)
    assert dict(s.device_ops) == pytest.approx(
        {"join": 3000e-9, "gather": 2500e-9})
    # chip 0 idle [1000,2000] (submit), [4000,9000] (1000 submit, 4000
    # wait); chip 1 idle [2000,11000] (3000 submit, 6000 wait): each
    # charged to the annotation that overlaps the gap most, halved
    gaps = dict(s.idle_gaps)
    assert gaps["bench.submit"] == pytest.approx(1000e-9 / 2)
    assert gaps["bench.wait"] == pytest.approx((5000e-9 + 9000e-9) / 2)


def test_union_merges_touching_and_nested_intervals():
    assert trace.union([(5, 6), (0, 2), (1, 3), (3, 4), (7, 9), (8, 8.5)]) \
        == [(0, 4), (5, 6), (7, 9)]


def test_a_recorded_tpu_trace():
    """A trace recorded on a TPU v5e: three calls of the replicated
    engine's quantized step inside the benchmark's annotations. The
    device stamps the first call's module before the host opened the
    stretch (the clocks differ by up to about a millisecond), so two
    of the three fall inside it."""
    events = trace.read_xspace(os.path.join(DATA, "tiny.xplane.pb"))
    modules = [e for e in events if e.line == trace.MODULES_LINE]
    assert len(modules) == 3
    s = trace.reduce(events)
    assert s.chips == 1
    assert s.window_s == pytest.approx(0.013085359)
    assert 0 < s.busy_s < s.window_s
    seconds, count = s.program_time(("jit__engine_fn_quantized",))
    assert count == 2 and 0 < seconds < s.window_s
    names = [name for name, _ in s.device_ops]
    assert "join_pallas.1" in names and all(" " not in n for n in names)
    gaps = dict(s.idle_gaps)
    assert gaps["bench.wait"] > 0.005      # three sleeps of 2 ms
    assert sum(gaps.values()) == pytest.approx(s.window_s - s.busy_s)


def test_peaks_are_keyed_by_device_kind():
    assert peaks.peak("TPU v5 lite", "hbm_bytes_per_s") == 819e9
    with pytest.raises(KeyError):
        peaks.peak("TPU v99", "hbm_bytes_per_s")


def test_byte_count_of_a_hand_worked_request():
    m = _roofline()
    # two districts of 3 vertices: {0,1,2} and {3,4,5}; q = 4
    district = np.array([0, 0, 0, 1, 1, 1])
    size = np.array([3, 3])
    # sources {0, 3}, targets {1, 4, 4}: pairs (0,1) (0,4) (0,4) (3,1)
    # (3,4) (3,4). District rows: 0, 1 (pair 0-1), 3, 4 (pair 3-4): 4
    # rows of 3 entries. Border rows: 0, 4 (0-4), 3, 1 (3-1): 4 rows of
    # 4 entries. 6 pairs of 4 + 4 bytes.
    got = m.request_bytes([0, 3], [1, 4, 4], district, size, 4)
    assert got == (4 * 3 + 4 * 4) * 2 + 6 * 8


def test_border_count_and_roofline_bound():
    m = _roofline()
    from chipbench import network
    net = network.continent((3, 3), (8, 8), 2, 15,
                            np.random.default_rng(1))
    q = m.border_count(net)
    # 12 boundary segments x 2 links x 2 endpoints, corners shared
    assert 40 <= q <= 48
    size = np.bincount(net.district)
    rng = np.random.default_rng(2)
    src = rng.integers(0, net.num_vertices, 32)
    dst = rng.integers(0, net.num_vertices, 32)
    need = m.request_bytes(src, dst, net.district, size, q)
    # never more than every row of both sides at its widest, plus pairs
    assert need <= (32 + 32) * 2 * max(q, 64) * 2 + 32 * 32 * 8
    assert need >= 32 * 32 * 8
