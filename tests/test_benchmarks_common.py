"""Benchmark-layer plumbing: the subprocess PYTHONPATH fix, the
telemetry sink round-trip, and the compare.py regression gates.

These run without jax — the telemetry/compare layer must stay importable
on a bare host so CI can gate results files from any runner.
"""
import json
import os
import subprocess
import sys

import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO_ROOT not in sys.path:       # benchmarks/ is a namespace package
    sys.path.insert(0, REPO_ROOT)   # rooted at the repo, not src/

from benchmarks import compare, telemetry  # noqa: E402
from benchmarks.common import subprocess_pythonpath  # noqa: E402


# -- subprocess PYTHONPATH (the implicit-cwd bug) ---------------------------

def test_subprocess_pythonpath_no_empty_components():
    """``"".split(os.pathsep)`` is ``[""]`` — the old join produced
    ``src:`` whose trailing empty component is an implicit cwd on the
    child's sys.path.  Unset and empty PYTHONPATH must both yield bare
    ``src``."""
    assert subprocess_pythonpath({}) == "src"
    assert subprocess_pythonpath({"PYTHONPATH": ""}) == "src"
    joined = subprocess_pythonpath({"PYTHONPATH": f"/x{os.pathsep}"})
    assert joined == os.pathsep.join(["src", "/x"])
    assert "" not in joined.split(os.pathsep)


def test_subprocess_pythonpath_preserves_inherited_entries():
    env = {"PYTHONPATH": os.pathsep.join(["/a", "", "/b"])}
    assert subprocess_pythonpath(env) == os.pathsep.join(["src", "/a",
                                                          "/b"])


def test_subprocess_child_has_no_empty_syspath_entry():
    """End-to-end: a child launched the way run_json_subprocess launches
    one must not have '' (implicit cwd) on sys.path from PYTHONPATH."""
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["PYTHONPATH"] = subprocess_pythonpath(env)
    out = subprocess.run(
        [sys.executable, "-c",
         "import sys, json; print(json.dumps(sys.path))"],
        env=env, capture_output=True, text=True, cwd=REPO_ROOT)
    paths = json.loads(out.stdout)
    # -c mode legitimately adds '' for the *script* dir as entry 0; any
    # OTHER empty entry would be the PYTHONPATH bug resurfacing
    assert "" not in paths[1:]
    assert any(p.endswith("src") for p in paths)


def test_subprocess_child_is_pinned_to_cpu(monkeypatch):
    """Children measure virtual host-device layouts; on an accelerator
    host they must never reach for the chip the parent holds, whatever
    platform the parent asked for."""
    from benchmarks.common import run_json_subprocess
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    out = run_json_subprocess(
        "import json, os; "
        "print(json.dumps({'platforms': os.environ['JAX_PLATFORMS']}))")
    assert out == {"platforms": "cpu", "backend": "cpu"}


# -- telemetry sink ---------------------------------------------------------

def test_telemetry_sink_round_trip(tmp_path):
    path = str(tmp_path / "BENCH_PR99.json")
    sink = telemetry.Sink(path, profile="quick")
    assert sink.pr == 99                     # parsed from the filename
    with sink.section("query"):
        sink.record("engine/batched-1024", 1.87, unit="us_per_call",
                    derived="qps=535,000")
        sink.record("engine/bytes", 4096, unit="bytes",
                    config={"devices": 8})
    sink.record("loose", 1.0, unit="info")   # outside any section
    sink.write()

    doc = json.loads((tmp_path / "BENCH_PR99.json").read_text())
    assert doc["schema_version"] == telemetry.SCHEMA_VERSION
    assert doc["pr"] == 99 and doc["profile"] == "quick"
    assert doc["machine"]["python"]
    sec = doc["sections"]["query"]
    assert sec["seconds"] >= 0.0
    assert {"rss_before_bytes", "rss_after_bytes",
            "peak_rss_bytes"} <= sec.keys()
    by_name = {r["name"]: r for r in doc["results"]}
    assert by_name["engine/batched-1024"]["section"] == "query"
    assert by_name["engine/bytes"]["config"] == {"devices": 8}
    assert by_name["loose"]["section"] is None


def test_telemetry_module_level_sink_is_optional(tmp_path):
    """record()/section() are no-ops without an active sink; with one,
    common.emit routes rows into it."""
    telemetry.record("ignored", 1.0)         # must not raise
    with telemetry.section("ignored"):
        pass
    sink = telemetry.start(str(tmp_path / "BENCH_PR1.json"))
    try:
        from benchmarks.common import emit
        with telemetry.section("s"):
            emit("a/b", 2.5, "note", unit="ms")
        assert sink.results == [{"section": "s", "name": "a/b",
                                 "value": 2.5, "unit": "ms",
                                 "derived": "note", "config": None}]
    finally:
        telemetry.stop()
    assert telemetry.current() is None


def test_telemetry_rss_probes_positive():
    assert telemetry.rss_bytes() > 0
    assert telemetry.peak_rss_bytes() >= telemetry.rss_bytes() // 2


# -- compare.py gates -------------------------------------------------------

def _doc(pr, rows, profile="quick"):
    return {"schema_version": 1, "pr": pr, "profile": profile,
            "argv": [], "machine": {}, "sections": {},
            "results": [{"section": "s", "name": n, "value": v,
                         "unit": u, "derived": "", "config": None}
                        for n, v, u in rows]}


def _write(tmp_path, name, doc):
    p = tmp_path / name
    p.write_text(json.dumps(doc))
    return str(p)


BASE_ROWS = [("engine/batched-1024", 2.0, "us_per_call"),
             ("load/goodput", 500_000.0, "qps"),
             ("engine/table-bytes", 1_000_000, "bytes"),
             ("load/shed-frac", 0.1, "info")]


def test_compare_self_is_clean(tmp_path):
    cur = _write(tmp_path, "BENCH_PR6.json", _doc(6, BASE_ROWS))
    assert compare.main([cur, cur]) == 0


def test_compare_latency_regression_trips(tmp_path):
    base = _write(tmp_path, "BENCH_PR5.json", _doc(5, BASE_ROWS))
    rows = [(n, v * (1.5 if n == "engine/batched-1024" else 1.0), u)
            for n, v, u in BASE_ROWS]
    cur = _write(tmp_path, "BENCH_PR6.json", _doc(6, rows))
    assert compare.main([cur, base]) == 1
    # within tolerance: clean
    rows = [(n, v * (1.2 if n == "engine/batched-1024" else 1.0), u)
            for n, v, u in BASE_ROWS]
    cur = _write(tmp_path, "BENCH_PR6b.json", _doc(6, rows))
    assert compare.main([cur, base]) == 0


def test_compare_throughput_and_bytes_direction(tmp_path):
    base = _write(tmp_path, "BENCH_PR5.json", _doc(5, BASE_ROWS))
    # qps DROP is a regression; qps growth is not
    drop = [(n, v * (0.5 if u == "qps" else 1.0), u)
            for n, v, u in BASE_ROWS]
    assert compare.main(
        [_write(tmp_path, "a.json", _doc(6, drop)), base]) == 1
    grow = [(n, v * (2.0 if u == "qps" else 1.0), u)
            for n, v, u in BASE_ROWS]
    assert compare.main(
        [_write(tmp_path, "b.json", _doc(6, grow)), base]) == 0
    # bytes gate is tight (2%): +5% growth fails even with warn-only
    bloat = [(n, v * (1.05 if u == "bytes" else 1.0), u)
             for n, v, u in BASE_ROWS]
    cur = _write(tmp_path, "c.json", _doc(6, bloat))
    assert compare.main([cur, base]) == 1
    assert compare.main([cur, base, "--warn-only-timing"]) == 1


def test_compare_warn_only_timing_downgrades(tmp_path):
    base = _write(tmp_path, "BENCH_PR5.json", _doc(5, BASE_ROWS))
    slow = [(n, v * (3.0 if n == "engine/batched-1024" else 1.0), u)
            for n, v, u in BASE_ROWS]
    cur = _write(tmp_path, "BENCH_PR6.json", _doc(6, slow))
    assert compare.main([cur, base]) == 1
    assert compare.main([cur, base, "--warn-only-timing"]) == 0


def test_compare_info_unit_never_gated(tmp_path):
    base = _write(tmp_path, "BENCH_PR5.json", _doc(5, BASE_ROWS))
    rows = [(n, v * (50.0 if u == "info" else 1.0), u)
            for n, v, u in BASE_ROWS]
    cur = _write(tmp_path, "BENCH_PR6.json", _doc(6, rows))
    assert compare.main([cur, base]) == 0


def test_compare_profile_mismatch_warns_not_fails(tmp_path, capsys):
    base = _write(tmp_path, "BENCH_PR5.json",
                  _doc(5, BASE_ROWS, profile="full"))
    cur = _write(tmp_path, "BENCH_PR6.json", _doc(6, BASE_ROWS))
    assert compare.main([cur, base]) == 0
    assert "profile mismatch" in capsys.readouterr().out


def test_compare_finds_previous_pr_baseline(tmp_path):
    _write(tmp_path, "BENCH_PR3.json", _doc(3, BASE_ROWS))
    p5 = _write(tmp_path, "BENCH_PR5.json", _doc(5, BASE_ROWS))
    cur = _write(tmp_path, "BENCH_PR6.json", _doc(6, BASE_ROWS))
    assert compare.find_baseline(cur, 6) == p5
    # no earlier file → self (trivially clean)
    only = str(tmp_path / "BENCH_PR3.json")
    assert compare.find_baseline(only, 3) == only


def test_compare_corrupt_json_clear_error(tmp_path):
    p = tmp_path / "BENCH_PR6.json"
    p.write_text("{not json")
    with pytest.raises(SystemExit, match="not valid JSON"):
        compare.main([str(p)])
    with pytest.raises(SystemExit, match="no such file"):
        compare.main([str(tmp_path / "missing.json")])


def test_report_rejects_corrupt_json(tmp_path, monkeypatch):
    """benchmarks.report must fail with a pointer, not a bare traceback,
    on a truncated results file."""
    from benchmarks import report
    p = tmp_path / "results.json"
    p.write_text('{"results": [')
    with pytest.raises(SystemExit, match="not valid JSON"):
        report.load(str(p))
