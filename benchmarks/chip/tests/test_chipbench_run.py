"""Whole runs at a tiny size on the CPU, without the look for a chip:
a cell defined only by new files is found and run; the comparison
passes on the program as it is and fails on the control and on faults
planted in the timed path; a metric that finds nothing to read fails
the run."""
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

import control  # noqa: E402
from chipbench.bench import Run, measure, run_cell  # noqa: E402
from chipbench.spec import load_cell  # noqa: E402

TINY = {"name": "tiny-2x2", "grid": [2, 2], "district": [8, 8],
        "border_links": 2, "weight_high": 15,
        "policy": {"engine": "replicated", "label_dtype": "uint16",
                   "rebuild": "install_now"}, "chips": 1}
TRIPS = {"driver": "closed_loop", "pool": 16,
         "pairs": {"kind": "trips", "per_request": 256, "origin_zipf": 1.0,
                   "same_district": 0.6, "hop_decay": 0.5},
         "check": {"sources": 256}}
MATRIX = {"driver": "closed_loop", "pool": 64,
          "pairs": {"kind": "matrix", "sources": 16, "targets": 256},
          "check": {"sources": 64}}
EXTRA_METRIC = '''"""calls.tiny: submit calls the window made."""


def read(run):
    return float(len(run.record.start))
'''
CELLS = ["tiny-trips", "tiny-matrix"]
SECONDS = 0.6


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A checkout holding only new files: a configuration, two mixes
    and a metric that no file of the benchmark names."""
    root = tmp_path_factory.mktemp("bench")
    harness = root / "bench"
    for sub in ("configs", "traffic"):
        (harness / sub).mkdir(parents=True)
    shutil.copytree(os.path.join(HARNESS, "metrics"), harness / "metrics")
    (harness / "metrics" / "calls.tiny.py").write_text(EXTRA_METRIC)
    (harness / "configs" / "tiny-2x2.json").write_text(json.dumps(TINY))
    for name, mix in zip(CELLS, (TRIPS, MATRIX)):
        (harness / "traffic" / f"{name}.json").write_text(json.dumps(mix))
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    bench["configs"] = [{"name": "tiny-2x2", "source": "test",
                         "file": "bench/configs/tiny-2x2.json",
                         "reduced": [], "why": "test"}]
    bench["workloads"] = [
        {"name": name, "config": "tiny-2x2", "traffic": name, "chips": 1,
         "why": "test"} for name in CELLS]
    for m in bench["end_to_end"]:
        if "workloads" in m:
            m["workloads"] = CELLS
    bench["per_layer"] = [
        {"name": "calls.tiny", "unit": "1", "better": "higher",
         "source": "host_clock", "layer": "front door and engine",
         "moves": "answers_per_s"},
        {"name": "submit_ms.matrix", "unit": "ms", "better": "lower",
         "source": "host_clock", "layer": "front door and engine",
         "moves": "answers_per_s", "workloads": ["tiny-matrix"]}]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return str(root)


def _cell(root, name):
    return load_cell(root, name, os.path.join(root, "bench"))


def _quiet(msg):
    pass


def test_new_files_make_a_cell_the_harness_finds(root):
    cell = _cell(root, "tiny-trips")
    assert cell.config["grid"] == [2, 2]
    assert cell.mix["pairs"]["per_request"] == 256
    assert [m["name"] for m in cell.end_to_end] == ["answers_per_s",
                                                    "setup_s"]
    # no workloads key: reported wherever the metric it moves is
    assert [m["name"] for m in cell.per_layer] == ["calls.tiny"]
    assert [m["name"] for m in _cell(root, "tiny-matrix").per_layer] == \
        ["calls.tiny", "submit_ms.matrix"]
    with pytest.raises(KeyError):
        _cell(root, "no-such-cell")


@pytest.mark.parametrize("name", CELLS)
def test_a_whole_run_is_correct(root, name):
    out = run_cell(_cell(root, name), 2**32 + 9, SECONDS, False,
                   time.perf_counter(), _quiet)
    assert out["correct"] is True and out["failed"] == 0
    assert set(out["metrics"]) == {"answers_per_s", "setup_s"}
    assert out["metrics"]["answers_per_s"]["value"] > 0
    assert list(out)[-1] == "checks"
    assert out["checks"]["wrong"] == {"value": 0, "limit": 0}
    assert out["checks"]["compared"]["value"] > 100
    json.dumps(out)


def test_a_traced_run_reports_the_discovered_metric(root):
    out = run_cell(_cell(root, "tiny-trips"), 5, SECONDS, True,
                   time.perf_counter(), _quiet)
    assert out["correct"] is True
    assert out["metrics"]["calls.tiny"]["value"] >= 1
    assert "answers_per_s" not in out["metrics"]


def test_a_metric_with_nothing_to_read_fails_the_run(root):
    """submit_ms.matrix reads the untraced part of the window; a run
    whose window lies wholly in the traced stretch has nothing there."""
    with pytest.raises(SystemExit, match="submit_ms.matrix"):
        run_cell(_cell(root, "tiny-matrix"), 5, 0.0, True,
                 time.perf_counter(), _quiet)


def _wrong_run(root, name, seed=11):
    run = Run(_cell(root, name), seed, SECONDS)
    run.deploy()
    return measure(run, False, time.perf_counter(), _quiet)


@pytest.mark.parametrize("name", CELLS)
def test_the_control_is_not_correct(root, name):
    run = Run(_cell(root, name), 13, SECONDS)
    run.deploy()
    reading = control.control_reading(run, _quiet)
    assert reading["correct"] is False
    assert reading["checks"]["wrong"] > 0
    # and the same deployment, back on its exact tables, is correct
    assert measure(run, False, time.perf_counter(), _quiet)["correct"]


def _alter_one_per_block(out):
    out = np.array(out, dtype=np.float32)
    out[::256] += 1.0
    return out


def _drop_half(out):
    out = np.array(out, dtype=np.float32)
    out[1::2] = np.inf
    return out


@pytest.mark.parametrize("fault", [_alter_one_per_block, _drop_half])
@pytest.mark.parametrize("name", CELLS)
def test_a_broken_timed_path_is_not_correct(root, name, fault,
                                            monkeypatch):
    from repro.edge.engine import BatchedQueryEngine
    answer = BatchedQueryEngine.query

    def broken(self, ss, ts):
        return fault(answer(self, ss, ts))
    monkeypatch.setattr(BatchedQueryEngine, "query", broken)
    monkeypatch.setattr(BatchedQueryEngine, "execute", broken)
    out = _wrong_run(root, name)
    assert out["correct"] is False
    assert out["checks"]["wrong"]["value"] > 0
