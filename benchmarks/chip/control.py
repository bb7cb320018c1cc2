#!/usr/bin/env python3
"""The control of the comparison that decides ``correct``, on the chip.

For each seed: deploy the cell once, serve a window with the label
tables stored one bit coarser than the configuration's exact integral
codes (the program's own lossy ``QuantSpec`` path, scale 2) and check
it, then serve and check the program as configured. The control has
to come out not correct; the program's reading is a normal run's.

    python3 benchmarks/chip/control.py --workload <cell> \\
        --seeds 11,12,13 --seconds 10

Prints one JSON line per seed: the control's numbers compared and the
program's result line. Not part of the benchmark's own runs.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402

from chipbench import host  # noqa: E402

# labels kept in even seconds only: the nearest precision below exact
# integral codes
LOSSY_SCALE = 2.0


def lossy(run) -> None:
    """Switch the deployment's serving tables to the lossy codes."""
    import numpy as np
    from repro.core.quantize import QuantSpec
    spec = QuantSpec(LOSSY_SCALE, np.uint16, lossless=False)
    run.system._resolve_quant = lambda label_dtype: spec
    run.system._engine = None


def exact(run) -> None:
    """Back to the configured tables."""
    del run.system._resolve_quant
    run.system._engine = None


def control_reading(run, log) -> dict:
    lossy(run)
    run.warm()
    rec = run.window(traced=False)
    exact(run)
    limits, failed = run.check(rec)
    for x in limits:
        log("control " + x.text())
    return {"correct": all(x.holds for x in limits), "failed": failed,
            "checks": {x.name: x.value for x in limits}}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    host.setup_environment()
    from chipbench.bench import Run, measure
    from chipbench.spec import load_cell

    cell = load_cell(host.ROOT, args.workload, host.HARNESS)
    host.require_chips(cell.chips)
    t_start = T_START
    for seed in (int(s) for s in args.seeds.split(",")):
        run = Run(cell, seed, args.seconds)
        run.deploy()
        control = control_reading(run, host.log)
        result = measure(run, False, t_start, host.log)
        print(json.dumps({"seed": seed, "control": control,
                          "program": result}), flush=True)
        t_start = time.perf_counter()


if __name__ == "__main__":
    main()
