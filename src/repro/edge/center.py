"""Computing center (§4.2): owns the border labels B, rebuilds them each
traffic epoch, answers rule-3 (cross-district) queries, forwards rule-2
queries, and pushes Border Auxiliary Shortcuts down to the edge servers.

Index versions are double-buffered: while version k+1 is building, version
k keeps serving (the paper instead lets edge servers fall back to the
Local Bound — both policies are modeled; see simulator.py).

Two rebuild paths:

* ``rebuild`` — from scratch with the configured ``builder`` ("reference"
  = Algorithm-1 pruned Dijkstra, "jax" = the dense staged pipeline; the
  two are bit-for-bit identical on integral weights — pinned in
  ``tests/test_update.py``);
* ``apply_delta`` — delta-scoped repair via ``repro.update``: classify
  the dirty edges, re-run only the touched builder stages, and
  invalidate only the districts whose shortcut inputs (their borders'
  B rows) actually moved.  Always routes through the jax pipeline (the
  repair is defined over its cached stage outputs) and is bit-for-bit
  equal to a full jax rebuild.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field

import numpy as np

from ..core.border_labeling import build_border_labels_reference
from ..core.graph import Graph
from ..core.labels import BorderLabels
from ..core.partition import Partition, borders_of
from ..core.shortcuts import border_shortcut_matrix
from ..update.delta import classify_delta
from ..update.incremental import IncrementalBuilder


@dataclass
class ComputingCenter:
    graph: Graph
    partition: Partition
    border_labels: BorderLabels | None = None
    version: int = 0
    last_build_seconds: float = 0.0
    # "reference" (Algorithm 1, fast CPU path) or "jax" (the staged dense
    # pipeline — the accelerator path, and the one apply_delta repairs)
    builder: str = "reference"
    _shortcut_cache: dict[int, np.ndarray] = field(default_factory=dict)
    # border lists depend on topology + partition only — weight updates
    # never move them, so they are computed once per deployment instead
    # of inside every shortcuts_for call
    _border_lists: list[np.ndarray] | None = field(default=None, repr=False)
    _inc: IncrementalBuilder | None = field(default=None, repr=False)

    def _incremental_builder(self) -> IncrementalBuilder:
        if self._inc is None:
            self._inc = IncrementalBuilder()
        return self._inc

    def rebuild(self, new_weights: np.ndarray | None = None) -> float:
        """Rebuild B from fresh edge weights; returns build seconds."""
        if new_weights is not None:
            self.graph = self.graph.with_weights(new_weights)
        t0 = time.perf_counter()
        if self.builder == "jax":
            self.border_labels = self._incremental_builder().build_full(
                self.graph, self.partition)
        else:
            self.border_labels = build_border_labels_reference(
                self.graph, self.partition)
        self.last_build_seconds = time.perf_counter() - t0
        self.version += 1
        self._shortcut_cache.clear()
        return self.last_build_seconds

    def apply_delta(self, new_weights: np.ndarray) -> dict:
        """Delta-scoped rebuild: repair B for a weight update and bump the
        version, invalidating only the shortcut matrices whose inputs
        moved.  Returns a report::

            {"seconds", "incremental", "delta", "stale_districts",
             "changed_rows", "noop"}

        ``stale_districts`` are the districts whose Border Auxiliary
        Shortcuts changed (their edge servers must reinstall);
        everything else keeps serving the same shortcuts.  A delta with
        no dirty edges is a no-op (no version bump).
        """
        delta = classify_delta(self.graph, self.partition, new_weights)
        if delta.is_empty and self.border_labels is not None:
            return {"seconds": 0.0, "incremental": True, "delta": delta,
                    "stale_districts": [], "noop": True,
                    "changed_rows": np.zeros(self.graph.num_vertices,
                                             dtype=bool)}
        g2 = self.graph.with_weights(new_weights)
        t0 = time.perf_counter()
        labels, rep = self._incremental_builder().apply_delta(
            g2, self.partition, delta)
        self.last_build_seconds = time.perf_counter() - t0
        self.graph = g2
        self.border_labels = labels
        self.version += 1
        # scoped invalidation: district i's shortcut matrix reads only the
        # B rows of its own borders — drop it iff one of those rows moved
        changed = rep["changed_rows"]
        stale = [i for i, b in enumerate(self._borders())
                 if len(b) and changed[b].any()]
        for i in stale:
            self._shortcut_cache.pop(i, None)
        return {"seconds": self.last_build_seconds,
                "incremental": rep["incremental"], "delta": delta,
                "stale_districts": stale, "changed_rows": changed,
                "noop": False}

    def apply_structural(self, g_new: Graph) -> dict:
        """Structural rebuild for a topology change (closures/openings):
        classify via ``repro.topo``, repair the index with the scoped
        structural path, bump the version, and invalidate only the
        shortcut matrices whose inputs moved.  Same report shape as
        ``apply_delta`` plus ``"border_changed"``.

        Border lists are topology-derived, so unlike the weight path
        they are re-derived whenever the border sets moved (and the
        whole shortcut cache dropped with them — stale border lists
        would index B with the wrong rows)."""
        from ..topo.structural import classify_structural
        delta = classify_structural(self.graph, self.partition, g_new)
        if delta.is_empty and self.border_labels is not None:
            self.graph = g_new      # fresh CSR identity, same topology
            return {"seconds": 0.0, "incremental": True, "delta": delta,
                    "stale_districts": [], "noop": True,
                    "border_changed": False,
                    "changed_rows": np.zeros(self.graph.num_vertices,
                                             dtype=bool)}
        t0 = time.perf_counter()
        labels, rep = self._incremental_builder().apply_structural(
            g_new, self.partition, delta)
        self.last_build_seconds = time.perf_counter() - t0
        self.graph = g_new
        self.border_labels = labels
        self.version += 1
        changed = rep["changed_rows"]
        if delta.border_changed or rep.get("border_changed"):
            self._border_lists = None
            self._shortcut_cache.clear()
            stale = list(range(self.partition.num_districts))
        else:
            stale = [i for i, b in enumerate(self._borders())
                     if len(b) and changed[b].any()]
            for i in stale:
                self._shortcut_cache.pop(i, None)
        return {"seconds": self.last_build_seconds,
                "incremental": rep["incremental"], "delta": delta,
                "stale_districts": stale, "changed_rows": changed,
                "border_changed": delta.border_changed, "noop": False}

    def _borders(self) -> list[np.ndarray]:
        if self._border_lists is None:
            self._border_lists = borders_of(self.graph, self.partition)
        return self._border_lists

    def shortcuts_for(self, district_id: int) -> np.ndarray:
        """Border Auxiliary Shortcuts pushed to one edge server."""
        assert self.border_labels is not None, "rebuild() first"
        if district_id not in self._shortcut_cache:
            b = self._borders()[district_id]
            self._shortcut_cache[district_id] = border_shortcut_matrix(
                self.border_labels, b)
        return self._shortcut_cache[district_id]

    def answer_cross(self, s: int, t: int) -> float:
        assert self.border_labels is not None
        return self.border_labels.query(s, t)

    def answer_cross_many(self, ss: np.ndarray, ts: np.ndarray,
                          use_kernels: bool = True) -> np.ndarray:
        """Rule-3 bucket: one dense join over gathered B rows (the
        label_join Pallas kernel on accelerator backends)."""
        assert self.border_labels is not None
        if use_kernels:
            from ..kernels.label_join import ops as lj
            return lj.join_gathered(self.border_labels.table, ss, ts)
        return self.border_labels.query_many(ss, ts)
