"""Shared benchmark helpers: timing, CSV emission, and the subprocess
runner + code template for multi-device sweeps (XLA_FLAGS must be set
before jax initializes, so those re-enter in a fresh interpreter)."""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

from . import telemetry


def subprocess_pythonpath(env: dict) -> str:
    """``src`` prepended to the inherited PYTHONPATH, empty components
    dropped: ``"".split(os.pathsep)`` yields ``[""]``, and a trailing
    empty component (``PYTHONPATH=src:``) is an implicit cwd entry on
    the child's ``sys.path``."""
    return os.pathsep.join(
        ["src"] + [p for p in env.get("PYTHONPATH", "").split(os.pathsep)
                   if p])


def run_json_subprocess(code: str, timeout: int = 560) -> dict:
    """Run a Python snippet in a fresh interpreter (PYTHONPATH=src, repo
    root cwd) and parse the last JSON line it prints.

    The children measure virtual host-device layouts only, so they are
    pinned to the CPU platform: on an accelerator host the parent may
    already hold the chip, and a child reaching for it would fail or
    hang. The returned dict says so under ``"backend"``; rows built from
    it carry that in their ``config``."""
    env = dict(os.environ)
    env["PYTHONPATH"] = subprocess_pythonpath(env)
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=timeout,
                         cwd=os.path.dirname(os.path.dirname(
                             os.path.abspath(__file__))))
    if out.returncode != 0:
        raise RuntimeError(out.stderr[-1500:])
    result = json.loads([l for l in out.stdout.splitlines()
                         if l.startswith("{")][-1])
    result["backend"] = "cpu"
    return result


_ENGINE_SWEEP_TEMPLATE = r"""
import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count="
                           + "%(devices)d")
import json, time
import numpy as np
from repro.core import (bfs_grow_partition, grid_partition,
                        grid_road_network)
from repro.edge import BatchedQueryEngine, EdgeSystem, ShardedBatchedEngine

%(setup)s
system = EdgeSystem.deploy(g, part)
args = (system.center.border_labels.table,
        [srv.augmented for srv in system.servers], part.assignment)
sharded = ShardedBatchedEngine(*args)
border = ShardedBatchedEngine(*args, shard_border=True)
replicated = BatchedQueryEngine(*args)
rng = np.random.default_rng(0)
out = {"devices": sharded.num_devices,
       "n": int(g.num_vertices),
       "q": int(system.center.border_labels.num_borders),
       "per_device_table_bytes": sharded.district_table_bytes_per_device(),
       "per_device_resident_bytes": sharded.size_bytes(),
       "border_resident_bytes": border.size_bytes(),
       "border_table_bytes_per_device": border.border_table_bytes_per_device(),
       "replicated_district_bytes": replicated.data.district_bytes_per_device(),
       "replicated_table_bytes": replicated.size_bytes(),
       "sweep": {}, "sweep_border": {}}
for b in %(batches)r:
    ss = rng.integers(0, g.num_vertices, size=b)
    ts = rng.integers(0, g.num_vertices, size=b)
    ref = replicated.query(ss, ts)
    np.testing.assert_array_equal(sharded.query(ss, ts), ref)
    np.testing.assert_array_equal(border.query(ss, ts), ref)
    for eng, key in ((sharded, "sweep"), (border, "sweep_border")):
        best = float("inf")
        for _ in range(5):
            t0 = time.perf_counter()
            eng.query(ss, ts)
            best = min(best, time.perf_counter() - t0)
        out[key][str(b)] = best
print(json.dumps(out))
"""


def engine_sweep_code(setup: str, devices: int,
                      batch_sizes: tuple[int, ...]) -> str:
    """ShardedBatchedEngine sweep snippet (replicated-B AND row-sharded-B
    layouts): ``setup`` must define ``g`` and ``part``; answers are
    asserted identical to the replicated engine before timing, and
    per-device resident bytes are reported for every layout."""
    return _ENGINE_SWEEP_TEMPLATE % {
        "setup": setup, "devices": devices, "batches": batch_sizes}


def timeit(fn, *args, repeats: int = 3, warmup: int = 1, **kwargs):
    """Returns (result, best_seconds)."""
    out = None
    for _ in range(warmup):
        out = fn(*args, **kwargs)
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        out = fn(*args, **kwargs)
        best = min(best, time.perf_counter() - t0)
    return out, best


def emit(name: str, value: float, derived: str = "",
         unit: str = "us_per_call", config: dict | None = None) -> None:
    """Print the historical ``name,value,derived`` CSV row AND record a
    structured ``{name, value, unit, derived, config}`` result into the
    active telemetry sink (``benchmarks.run --json``), if any.  ``unit``
    tells ``compare.py`` which direction is a regression."""
    print(f"{name},{value:.3f},{derived}")
    telemetry.record(name, value, unit=unit, derived=derived, config=config)
