"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.  ``--only <prefix>``
filters suites; ``--json BENCH_PR6.json`` additionally records every
row into the structured telemetry sink (``benchmarks/telemetry.py``)
with per-suite RSS/wall sections, producing the perf-trajectory file
``benchmarks/compare.py`` gates against.  ``--quick`` runs the reduced
CI sweeps for the suites that support them.

The documented single command for a PR's telemetry baseline::

    PYTHONPATH=src:. python -m benchmarks.run --quick --json BENCH_PR6.json
"""
from __future__ import annotations

import argparse
import inspect
import sys
import traceback

from . import telemetry


def _call(fn, quick: bool) -> None:
    if "quick" in inspect.signature(fn).parameters:
        fn(quick=quick)
    else:
        fn()


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--only", default="", help="prefix filter")
    ap.add_argument("--json", default="",
                    help="write structured results to this "
                         "BENCH_PR<N>.json (PR ordinal parsed from the "
                         "name; override with --pr)")
    ap.add_argument("--pr", type=int, default=None,
                    help="PR ordinal recorded in the JSON")
    ap.add_argument("--quick", action="store_true",
                    help="reduced sweeps (the CI smoke profile)")
    args = ap.parse_args()

    from . import (bench_edge, bench_indexing, bench_ingest,
                   bench_kernels, bench_lm, bench_load,
                   bench_oracle_sharding, bench_query, bench_topology,
                   bench_update)
    suites = {
        "indexing": bench_indexing.run,   # Table 2
        "query": bench_query.run,         # Fig. 5
        "edge": bench_edge.run,           # §5 dynamic scenario
        "kernels": bench_kernels.run,
        "lm": bench_lm.run,
        "oracle_sharding": bench_oracle_sharding.run,  # §Perf (paper side)
        "update": bench_update.run,       # incremental repair sweep
        "topology": bench_topology.run,   # closures + migration (repro.topo)
        "load": bench_load.run,           # open-loop million-user harness
        "ingest": bench_ingest.run,       # continent-scale ingest + quantize
    }
    sink = None
    if args.json:
        sink = telemetry.start(args.json, pr=args.pr,
                               profile="quick" if args.quick else "full")
    print("name,us_per_call,derived")
    failed = []
    for name, fn in suites.items():
        if args.only and not name.startswith(args.only):
            continue
        try:
            with telemetry.section(name):
                _call(fn, args.quick)
        except Exception:    # noqa: BLE001
            traceback.print_exc()
            failed.append(name)
    if sink is not None:
        path = sink.write()
        print(f"telemetry: {len(sink.results)} results from "
              f"{len(sink.sections)} sections -> {path}", file=sys.stderr)
    if failed:
        print(f"FAILED suites: {failed}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
