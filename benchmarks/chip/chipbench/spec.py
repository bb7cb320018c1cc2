"""Finding a cell's pieces by name.

``BENCHMARK.json`` at the checkout's root lists the configurations,
cells and metrics. Everything that belongs to one of them sits in a
file of its own, found by name under the harness directory:

* a configuration: the file its ``BENCHMARK.json`` entry names;
* a traffic mix: ``traffic/<traffic>.json``;
* a metric: ``metrics/<metric>.py``, exposing ``read(run)``, which
  returns the metric's value or None where the run holds nothing to
  read.

So a new configuration, mix or metric is new files plus new entries,
with no edit to a file that is there.
"""
from __future__ import annotations

import importlib.util
import json
import os
from dataclasses import dataclass


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    mix: dict
    end_to_end: list[dict]
    per_layer: list[dict]
    harness_dir: str

    def reader(self, metric: str):
        """The ``read`` function of ``metrics/<metric>.py``."""
        path = os.path.join(self.harness_dir, "metrics", metric + ".py")
        spec = importlib.util.spec_from_file_location(
            "chipbench_metric_" + metric.replace(".", "_").replace("-", "_"),
            path)
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        return module.read


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(root: str, name: str, harness_dir: str) -> Cell:
    """The cell ``name`` of ``<root>/BENCHMARK.json``."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; BENCHMARK.json has "
                       f"{sorted(cells)}")
    cell = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    with open(os.path.join(root, configs[cell["config"]]["file"])) as f:
        config = json.load(f)
    with open(os.path.join(harness_dir, "traffic",
                           cell["traffic"] + ".json")) as f:
        mix = json.load(f)
    e2e = [m for m in bench["end_to_end"] if _reports(m, name)]
    moved = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in moved)]
    return Cell(name, int(cell["chips"]), config, mix, e2e, per_layer,
                harness_dir)
