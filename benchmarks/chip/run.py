#!/usr/bin/env python3
"""Run one cell of the distance-query benchmark once, on the chip.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

Run from the root of a checkout. The cell is an entry of
``BENCHMARK.json``; its configuration, traffic mix and metrics are
found by name under this directory (see ``chipbench/spec.py``). The
run refuses anything but a TPU with as many chips as the cell asks
for, and then exits non-zero without a result. Otherwise the last line
of standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer metrics), ``device``, with ``--trace 1``
``breakdown``, and last ``checks``, each number compared with its
limit. Progress and the checks go to standard error.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402

from chipbench import host  # noqa: E402


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    host.setup_environment()
    from chipbench.bench import run_cell
    from chipbench.spec import load_cell

    cell = load_cell(host.ROOT, args.workload, host.HARNESS)
    host.require_chips(cell.chips)
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      T_START, host.log)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
