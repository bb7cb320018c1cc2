"""The program's spans in the harness (CPU): self time and the readers
on a hand-made span list, the charge of idle gaps to nested spans and
the clock offset on hand-made event lists, a trace recorded on a TPU
v5e, and a tiny traced run of ``breakdown.py``."""
from __future__ import annotations

import json
import os
import shutil
import sys
import time

import numpy as np
import pytest

HARNESS = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(HARNESS))
sys.path.insert(0, HARNESS)
sys.path.insert(0, os.path.join(ROOT, "src"))

import breakdown  # noqa: E402
from chipbench import spans, spantrace, trace  # noqa: E402
from chipbench.drivers import ClosedLoopRecord  # noqa: E402
from chipbench.spec import load_cell  # noqa: E402
from chipbench.trace import Event  # noqa: E402
from repro.obs import Span  # noqa: E402

DATA = os.path.join(HARNESS, "tests", "data")
TPU0, HOST, PY = "/device:TPU:0", "/host:CPU", "python"
MS = 1_000_000


def _read(metric):
    """The ``read`` of ``metrics/<metric>.py``, as the harness loads
    it."""
    cell = load_cell(ROOT, "sparse-trips", HARNESS)
    return cell.reader(metric)


# -- readers on a hand-made span list ----------------------------------------

T0 = 100.0              # the window's start, perf_counter seconds


def _submit(ids, at_ms, route, dispatch, fetch, wrap, fold=0.0):
    """The spans of one submit starting ``at_ms`` into the window; the
    other arguments are self times in ms. The submit's own self time is
    0.05 ms, the plan's 0.02 ms."""
    rid = next(ids)
    t = int((T0 * 1e3 + at_ms) * MS)
    out = []

    def child(name, ms, parent=rid):
        nonlocal t
        sid = next(ids)
        out.append(Span(name, sid, parent, rid, t, t + int(ms * MS), None))
        t += int(ms * MS)
        return sid

    start = t
    t += int(0.03 * MS)
    child("repro.plan", 0.02)
    child("repro.route", route)
    child("repro.dispatch", dispatch)
    child("repro.fetch", fetch)
    wrap_start = t
    wid = next(ids)
    if fold:
        t += int(wrap * MS)
        child("repro.fold", fold, parent=wid)
    else:
        t += int(wrap * MS)
    out.append(Span("repro.wrap", wid, rid, rid, wrap_start, t, None))
    t += int(0.02 * MS)
    out.append(Span("repro.submit", rid, 0, rid, start, t, None))
    return out, (start / 1e9 - T0 - 1e-6, t / 1e9 - T0 + 1e-6)


class _Run:
    def __init__(self, span_list, calls, untraced_s):
        self.spans = span_list
        self.record = ClosedLoopRecord(
            np.array([a for a, _ in calls]), np.array([b for _, b in calls]),
            np.zeros(len(calls), dtype=np.int64), [], T0)
        self.untraced_s = untraced_s


def _hand_run():
    ids = iter(range(1, 10_000))
    span_list, calls = [], []
    for at, args in [(0.0, (0.4, 0.2, 0.3, 0.01)),
                     (5.0, (0.6, 0.2, 0.5, 0.01, 0.2)),      # folds
                     (10.0, (0.4, 0.2, 30.0, 0.01)),         # a stall
                     (60.0, (0.4, 0.2, 0.3, 0.01))]:         # in the stretch
        got, call = _submit(ids, at, *args)
        span_list += got
        calls.append(call)
    deploy = [Span("repro.deploy.center", 9000, 0, 9000, 0, 3 * MS, None)]
    deploy += [Span("repro.deploy.server", 9001 + i, 0, 9001 + i,
                    (3 + i) * MS, (4 + i) * MS, {"district": i})
               for i in range(4)]
    return _Run(deploy + span_list, calls, untraced_s=0.05)


def test_self_time_subtracts_children():
    run = _hand_run()
    own = spans.self_ns(run.spans)
    by = {s.span_id: s for s in run.spans}
    for sid, ns in own.items():
        s = by[sid]
        kids = sum(k.end_ns - k.start_ns for k in run.spans
                   if k.parent_id == sid)
        assert ns == s.end_ns - s.start_ns - kids
    wraps = [sid for sid, s in by.items() if s.name == "repro.wrap"]
    assert own[wraps[1]] == int(0.01 * MS)      # the fold is its child


def test_the_readers_on_a_hand_made_span_list():
    run = _hand_run()
    kept = spans.kept_submits(run)
    # the stall and the submit in the traced stretch are left out
    assert len(kept) == 2
    assert _read("route_ms.trips")(run) == pytest.approx(0.5, abs=1e-6)
    assert _read("dispatch_ms.trips")(run) == pytest.approx(0.2, abs=1e-6)
    assert _read("fetch_ms.trips")(run) == pytest.approx(0.4, abs=1e-6)
    # submit 0.05 + plan 0.02 + wrap 0.01 + fold 0.2 / 2
    assert _read("front_door_ms.trips")(run) == pytest.approx(0.18,
                                                              abs=1e-6)
    four = sum(_read(m)(run) for m in ("route_ms.matrix", "dispatch_ms.matrix",
                                       "fetch_ms.matrix",
                                       "front_door_ms.matrix"))
    assert four == pytest.approx(spans.harness_ms(run), abs=0.003)
    assert _read("deploy_center_s")(run) == pytest.approx(0.003)
    assert _read("deploy_servers_s")(run) == pytest.approx(0.004)
    stalls = spans.stall_holders(run.spans, run.record)
    assert len(stalls) == 1 and stalls[0][2] == "repro.fetch"
    assert stalls[0][3] == pytest.approx(30.0)


def test_the_readers_find_nothing_without_spans():
    run = _hand_run()
    run.spans = []
    for m in breakdown.metric_names(load_cell(ROOT, "dense-matrix",
                                              HARNESS)):
        assert _read(m)(run) is None
    del run.spans
    assert _read("route_ms.trips")(run) is None


# -- nested charging and the offset on hand-made event lists -----------------

def _host(name, start, dur):
    return Event(HOST, PY, name, start, dur)


def test_innermost_splits_time_among_nested_spans():
    segs = list(spans.innermost([(0, 100, "a"), (10, 40, "b"),
                                 (20, 30, "c"), (60, 120, "d"),
                                 (200, 210, "e")]))
    assert segs == [(0, 10, "a"), (10, 20, "b"), (20, 30, "c"),
                    (30, 40, "b"), (40, 60, "a"), (60, 100, "d"),
                    (200, 210, "e")]


def test_idle_gaps_are_charged_to_the_innermost_span():
    events = [
        _host(trace.STRETCH, 0, 1000),
        _host("bench.submit", 0, 500),
        _host("repro.submit", 50, 440),
        _host("repro.route", 100, 200),
        _host("repro.dispatch", 300, 50),
        _host("repro.fetch", 350, 100),
        _host("bench.wait", 600, 300),
        # the device is busy [380, 420] and [950, 1000]
        Event(TPU0, trace.OPS_LINE, "%join = f32[256]", 380, 40),
        Event(TPU0, trace.OPS_LINE, "%fusion = f32[256]", 950, 50),
        Event(TPU0, trace.MODULES_LINE, "jit_other(1)", 380, 40),
    ]
    s = spantrace.reduce(events)
    assert not s.offset.found and s.offset.delta == 0
    assert s.window_s == pytest.approx(1000e-9)
    assert s.busy_s == pytest.approx(90e-9)
    gaps = {k: v * 1e9 for k, v in s.idle_gaps}
    # [0, 380]: bench.submit 50, repro.submit 50, route 200, dispatch 50,
    # fetch 30; [420, 950]: fetch 30, repro.submit 40, bench.submit 10,
    # untracked 100, bench.wait 300, untracked 50
    assert gaps == pytest.approx({
        "bench.submit": 60, "repro.submit": 90, "repro.route": 200,
        "repro.dispatch": 50, "repro.fetch": 60, "bench.wait": 300,
        trace.UNTRACKED: 150})
    assert sum(gaps.values()) == pytest.approx(1000 - 90)


def test_a_planted_offset_is_recovered():
    delta = 812_345.0           # host = device + delta, ns
    events = [_host(trace.STRETCH, 0, 40 * MS)]
    for k in range(6):
        at = (1 + 6 * k) * MS
        events += [_host("repro.dispatch", at, 0.2 * MS),
                   _host("repro.fetch", at + 0.3 * MS, 2 * MS)]
        # the step starts 0.8..1.3 us after the dispatch opens and ends
        # 1.0..1.5 us before the fetch closes, on the host's clock
        start = at + 800 + 100 * k
        end = at + 2.3 * MS - 1000 - 100 * (5 - k)
        events += [Event(TPU0, trace.MODULES_LINE,
                         "jit__engine_fn_quantized(3)", start - delta,
                         end - start),
                   Event(TPU0, trace.OPS_LINE, "%join", start - delta,
                         end - start)]
    off = spantrace.estimate_offset(events)
    assert off.found and off.pairs == 6
    assert off.lo == pytest.approx(delta - 800)
    assert off.hi == pytest.approx(delta + 1000)
    assert abs(off.delta - delta) < 1000
    # with the offset removed, the device is idle only outside the steps
    s = spantrace.reduce(events)
    gaps = dict(s.idle_gaps)
    assert "repro.dispatch" in gaps and "repro.fetch" in gaps
    assert gaps["repro.fetch"] * 1e9 < 6 * 2000
    # a step that ended after its fetch closed: no offset fits
    events.append(Event(TPU0, trace.MODULES_LINE, "jit__engine_fn(4)",
                        37 * MS - delta, 10 * MS))
    events += [_host("repro.dispatch", 37 * MS, 0.1 * MS),
               _host("repro.fetch", 37.2 * MS, 0.5 * MS)]
    bad = spantrace.estimate_offset(events)
    assert not bad.found and bad.delta == 0.0


def test_a_recorded_tpu_trace_with_program_spans():
    """Four 256-trip submits recorded on a TPU v5e with the program's
    spans on (``record_spans_trace.py``)."""
    path = os.path.join(DATA, "spans.xplane.pb")
    assert os.path.getsize(path) <= 100_000
    events = spantrace.read_xspace(path)
    names = {e.name for e in events}
    assert {"repro.submit", "repro.plan", "repro.route", "repro.dispatch",
            "repro.fetch", "repro.wrap", "bench.submit"} <= names
    off = spantrace.estimate_offset(events)
    assert off.found and off.pairs >= 3
    assert 0 <= off.width < 2 * MS
    s = spantrace.reduce(events)
    gaps = dict(s.idle_gaps)
    assert sum(gaps.values()) == pytest.approx(s.window_s - s.busy_s)
    program = sum(v for k, v in gaps.items() if k.startswith("repro."))
    assert program > gaps.get("bench.submit", 0.0)


# -- a tiny traced run -------------------------------------------------------

TINY = {"name": "tiny-2x2", "grid": [2, 2], "district": [8, 8],
        "border_links": 2, "weight_high": 15,
        "policy": {"engine": "replicated", "label_dtype": "uint16",
                   "rebuild": "install_now"}, "chips": 1}
MIXES = {
    "tiny-trips": {"driver": "closed_loop", "pool": 16,
                   "pairs": {"kind": "trips", "per_request": 256,
                             "origin_zipf": 1.0, "same_district": 0.6,
                             "hop_decay": 0.5},
                   "check": {"sources": 256}},
    "tiny-matrix": {"driver": "closed_loop", "pool": 64,
                    "pairs": {"kind": "matrix", "sources": 16,
                              "targets": 256},
                    "check": {"sources": 64}}}


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    root = tmp_path_factory.mktemp("spans")
    harness = root / "bench"
    for sub in ("configs", "traffic"):
        (harness / sub).mkdir(parents=True)
    shutil.copytree(os.path.join(HARNESS, "metrics"), harness / "metrics")
    (harness / "configs" / "tiny-2x2.json").write_text(json.dumps(TINY))
    for name, mix in MIXES.items():
        (harness / "traffic" / f"{name}.json").write_text(json.dumps(mix))
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    bench["configs"] = [{"name": "tiny-2x2", "source": "test",
                         "file": "bench/configs/tiny-2x2.json",
                         "reduced": [], "why": "test"}]
    bench["workloads"] = [{"name": name, "config": "tiny-2x2",
                           "traffic": name, "chips": 1, "why": "test"}
                          for name in MIXES]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return str(root)


@pytest.mark.parametrize("name", sorted(MIXES))
def test_a_tiny_traced_run_reports_every_span_metric(tiny_root, name):
    from repro import obs
    cell = load_cell(tiny_root, name, os.path.join(tiny_root, "bench"))
    try:
        out = breakdown.breakdown(cell, 2**31 + 77, 1.2, 1, 0.2,
                                  time.perf_counter(), lambda msg: None)
    finally:
        obs.disable()
        obs.reset()
    kind = "trips" if name.endswith("trips") else "matrix"
    assert set(out["metrics"]) == {
        f"route_ms.{kind}", f"dispatch_ms.{kind}", f"fetch_ms.{kind}",
        f"front_door_ms.{kind}", "deploy_servers_s", "deploy_center_s"}
    assert all(v is not None and v >= 0 for v in out["metrics"].values())
    assert out["correct"] is True and out["kept_submits"] > 0
    four = sum(out["metrics"][f"{m}.{kind}"]
               for m in breakdown.SUBMIT_METRICS)
    # the four add up to the harness's time of the same calls, less the
    # call into submit and the span bookkeeping around it
    assert four <= out["harness_ms"]
    assert four >= 0.8 * out["harness_ms"]
    setup = out["setup"]
    assert (setup["deploy_center_s"] + setup["deploy_servers_s"]
            <= setup["setup_s"])
    assert len(out["cost_submit_ms"]["on"]) == 1
    assert len(out["cost_submit_ms"]["off"]) == 1
    assert out["dropped_spans"] == 0
    json.dumps(out)
