#!/usr/bin/env python3
"""Record the small profiler trace with the program's spans that
``test_chipbench_spans.py`` reads (``data/spans.xplane.pb``), on the
chip.

    python3 benchmarks/chip/tests/record_spans_trace.py <out.xplane.pb>

Deploys a 2x2-district network of 8x8-vertex districts, serves it with
the replicated engine on uint16 tables, and with ``repro.obs``
recording traces four 256-trip ``submit`` calls inside the benchmark's
``bench.stretch`` annotation, each after a 2 ms ``bench.wait``.
"""
from __future__ import annotations

import os
import shutil
import sys
import tempfile
import time

HARNESS = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HARNESS)

from chipbench import host  # noqa: E402

CONFIG = {"grid": [2, 2], "district": [8, 8], "border_links": 2,
          "weight_high": 15}
CALLS = 4


def record(out_path: str) -> None:
    import jax
    import numpy as np
    from repro import obs
    from repro.edge import EdgeSystem
    from repro.serve import ServingPolicy

    from chipbench import bench, trace

    rng = np.random.default_rng(7)
    net = bench.make_network(CONFIG, 7)
    g, part = bench.to_program(net)
    system = EdgeSystem.deploy(g, part, builder="jax")
    service = system.service(ServingPolicy(engine="replicated",
                                           label_dtype="uint16"))
    batches = [(rng.integers(0, net.num_vertices, 256),
                rng.integers(0, net.num_vertices, 256))
               for _ in range(CALLS + 2)]
    for ss, ts in batches[:2]:                  # compile outside the trace
        service.submit(ss, ts)
    obs.enable()
    with tempfile.TemporaryDirectory() as logdir:
        jax.profiler.start_trace(logdir)
        with jax.profiler.TraceAnnotation(trace.STRETCH):
            for ss, ts in batches[2:]:
                with jax.profiler.TraceAnnotation("bench.wait"):
                    time.sleep(0.002)
                with jax.profiler.TraceAnnotation("bench.submit"):
                    service.submit(ss, ts)
        jax.profiler.stop_trace()
        obs.disable()
        (path,) = [os.path.join(d, f) for d, _, fs in os.walk(logdir)
                   for f in fs if f.endswith(".xplane.pb")]
        shutil.copyfile(path, out_path)
    host.log(f"wrote {out_path}: {os.path.getsize(out_path)} bytes")


if __name__ == "__main__":
    host.setup_environment()
    host.require_chips(1)
    record(sys.argv[1])
