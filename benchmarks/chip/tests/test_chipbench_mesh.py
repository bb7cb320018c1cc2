"""The four-chip cell on the CPU: a whole tiny run of a 4-chip cell
defined only by new files, under four virtual devices; the sizes
behind the real configuration's layout; and the readers of the sharded
step on a hand-made trace."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from types import SimpleNamespace

import numpy as np
import pytest

HARNESS = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(HARNESS))
sys.path.insert(0, HARNESS)
sys.path.insert(0, os.path.join(ROOT, "src"))

from chipbench import mesh, trace  # noqa: E402
from chipbench.bench import make_network  # noqa: E402
from chipbench.spec import load_cell  # noqa: E402
from chipbench.trace import Event  # noqa: E402

TPU = ["/device:TPU:%d" % i for i in range(4)]
HOST = "/host:CPU"
# B is far below the auto thresholds at this size, so the tiny
# configuration pins what the auto-pick chooses at the real one
TINY = {"name": "tiny-mesh", "grid": [3, 3], "district": [8, 8],
        "border_links": 5, "weight_high": 15,
        "policy": {"engine": "auto", "shard_border": True,
                   "label_dtype": "uint16", "rebuild": "install_now"},
        "chips": 4}
TRIPS = {"driver": "closed_loop", "pool": 8,
         "pairs": {"kind": "trips", "per_request": 1024, "origin_zipf": 1.0,
                   "same_district": 0.6, "hop_decay": 0.5},
         "check": {"sources": 64}}
RUN = """
import json, sys, time
import jax
assert len(jax.devices()) == 4
sys.path.insert(0, {harness!r})
from chipbench.bench import run_cell
from chipbench.spec import load_cell
cell = load_cell({root!r}, "tiny-mesh4", {bench!r})
out = run_cell(cell, 2**33 + 5, 0.6, False, time.perf_counter(),
               lambda msg: None)
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A checkout whose 4-chip cell is new files only."""
    root = tmp_path_factory.mktemp("mesh")
    harness = root / "bench"
    for sub in ("configs", "traffic"):
        (harness / sub).mkdir(parents=True)
    shutil.copytree(os.path.join(HARNESS, "metrics"), harness / "metrics")
    (harness / "configs" / "tiny-mesh.json").write_text(json.dumps(TINY))
    (harness / "traffic" / "tiny-trips1024.json").write_text(
        json.dumps(TRIPS))
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    bench["configs"] = [{"name": "tiny-mesh", "source": "test",
                         "file": "bench/configs/tiny-mesh.json",
                         "reduced": [], "why": "test"}]
    bench["workloads"] = [{"name": "tiny-mesh4", "config": "tiny-mesh",
                           "traffic": "tiny-trips1024", "chips": 4,
                           "why": "test"}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = ["tiny-mesh4"]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return str(root)


def test_the_four_chip_cell_is_found_with_its_metrics(root):
    cell = load_cell(root, "tiny-mesh4", os.path.join(root, "bench"))
    assert cell.chips == 4
    assert cell.mix["pairs"]["per_request"] == 1024
    assert [m["name"] for m in cell.end_to_end] == ["answers_per_s",
                                                    "setup_s"]
    real = load_cell(ROOT, "mesh4-trips", HARNESS)
    assert real.chips == 4 and real.config["chips"] == 4
    assert {m["name"] for m in real.per_layer} == {
        "submit_ms.mesh4", "device_idle_share.mesh4", "step_us.mesh4",
        "collective_us.mesh4"}
    # the program picks the engine, B's placement and the dtype (see the
    # next test)
    assert real.config["policy"] == {"engine": "auto", "label_dtype": "auto",
                                     "rebuild": "install_now"}


def test_a_whole_tiny_run_on_four_devices_is_correct(root):
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=4")
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(ROOT, "src")]
        + env.get("PYTHONPATH", "").split(os.pathsep))
    code = RUN.format(harness=HARNESS, root=root,
                      bench=os.path.join(root, "bench"))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=500,
                         cwd=root)
    assert out.returncode == 0, out.stderr[-2000:]
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert result["correct"] is True and result["failed"] == 0
    assert result["checks"]["wrong"]["value"] == 0
    assert result["checks"]["compared"]["value"] > 100
    assert result["metrics"]["answers_per_s"]["value"] > 0
    assert result["attempted"] % 1024 == 0


def _auto_sizes(config, seed, border_links):
    """(B's float32 bytes n * q * 4, the index estimate: B plus every
    district's dense table, in float32, q) of the generated network."""
    net = make_network(dict(config, border_links=border_links), seed)
    cross = net.district[net.u] != net.district[net.v]
    q = len(np.unique(np.concatenate([net.u[cross], net.v[cross]])))
    n = net.num_vertices
    return (n * q * 4,
            4 * (n * q + int((np.bincount(net.district) ** 2).sum())), q)


@pytest.mark.parametrize("seed", [1, 2**31 + 263, 4100000033])
def test_the_real_configuration_is_sharded_and_quantized_by_auto(seed):
    """Without deploying: the generated network's B is above the
    row-sharding threshold and its index above the quantization one, so
    the auto-pick shards B and stores uint16 codes with nothing pinned.
    One border link fewer a boundary would keep B replicated."""
    from repro.edge.router import QUANT_AUTO_BYTES, SHARD_BORDER_AUTO_BYTES
    config = json.load(open(os.path.join(HARNESS, "configs",
                                         "mesh4-8x8.json")))
    assert config["grid"] == [8, 8] and config["border_links"] == 5
    assert "shard_border" not in config["policy"]
    border, index, q = _auto_sizes(config, seed, 5)
    assert border > SHARD_BORDER_AUTO_BYTES * 1.05
    assert index > QUANT_AUTO_BYTES
    assert 1024 < q < 1200
    border, _, _ = _auto_sizes(config, seed, 4)
    assert border < SHARD_BORDER_AUTO_BYTES


def _summary(step_name, answers=True):
    events = [Event(HOST, "python", trace.STRETCH, 0, 100_000)]
    for i, plane in enumerate(TPU):
        for k in range(2):              # two steps a chip
            t0 = 10_000 + 40_000 * k
            events += [
                Event(plane, trace.MODULES_LINE, f"{step_name}({k + 9})",
                      t0, 20_000),
                Event(plane, trace.OPS_LINE,
                      "%pmin.14 = s32[2048,1101] all-reduce(%a)", t0,
                      6_000 + 1_000 * i),
                Event(plane, trace.OPS_LINE, "%join_pallas.1 = f32[4,1,256]"
                      " custom-call(%b, %c)", t0 + 7_000, 8_000),
                Event(plane, trace.OPS_LINE, "%fusion.3 = u16[1024] "
                      "fusion(%e)", t0 + 17_000, 2_000)]
            if answers:
                events.append(Event(plane, trace.OPS_LINE,
                                    "%pmin.15 = f32[1024] all-reduce(%d)",
                                    t0 + 16_000, 1_000))
    return trace.reduce(events)


@pytest.mark.parametrize("step_name", ["jit__sharded_step",
                                       "jit__device_fn"])
def test_the_step_readers_on_a_hand_made_trace(step_name):
    run = SimpleNamespace(trace=_summary(step_name))
    # 8 executions of 20 us over four chips
    assert mesh.step_us(run) == pytest.approx(20.0)
    # per chip and step: the border rows' all-reduce, 6-9 us (7.5 on
    # average), and the answers', 1 us
    assert mesh.collective_us(run) == pytest.approx(8.5)


def test_the_step_readers_find_nothing_without_the_step():
    assert mesh.step_us(SimpleNamespace(trace=None)) is None
    assert mesh.collective_us(SimpleNamespace(trace=None)) is None
    run = SimpleNamespace(trace=_summary("jit__engine_fn_quantized"))
    assert mesh.step_us(run) is None
    assert mesh.collective_us(run) is None
    # one all-reduce off the trace's op list reads nothing, not less
    run = SimpleNamespace(trace=_summary("jit__sharded_step", answers=False))
    assert mesh.step_us(run) == pytest.approx(20.0)
    assert mesh.collective_us(run) is None
    assert mesh.COLLECTIVE.match("all-reduce-start.2")
    assert mesh.COLLECTIVE.match("pmin.15")
    assert not mesh.COLLECTIVE.match("fusion.pmin")


def test_the_async_halves_of_one_all_reduce_count_as_one():
    ops = [("all-reduce-start.1", 4e-6), ("all-reduce-done.1", 2e-6),
           ("fusion", 1e-6)]
    trace_ = SimpleNamespace(device_ops=ops,
                             program_time=lambda jits: (8e-5, 4))
    assert mesh.collective_us(SimpleNamespace(trace=trace_)) is None
    ops += [("all-reduce-start.2", 2e-6), ("all-reduce-done.2", 2e-6)]
    # (4 + 2 + 2 + 2) us over 4 executions
    assert mesh.collective_us(SimpleNamespace(trace=trace_)) == \
        pytest.approx(2.5)
