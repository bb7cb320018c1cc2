"""Per-district local indexes L_i (plain) and L_i⁺ (shortcut-augmented).

An edge server owns one LocalIndex: labels in local vertex numbering plus
the maps back to global ids. ``plain`` labels (no shortcuts) are what the
server can build *by itself* from its own district subgraph — they power
the Local Bound fallback (Theorem 3) while the computing center is still
rebuilding B. ``augmented`` labels additionally fold in the Border
Auxiliary Shortcuts pushed down by the center and answer same-district
queries globally-exactly (Theorem 2).
"""
from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np

from .border_labeling import minplus
from .graph import Graph
from .labels import BorderLabels, SparseLabels
from .partition import Partition, borders_of
from .pll import pll_subgraph
from .shortcuts import border_shortcut_matrix, shortcut_edges

INF = np.float32(np.inf)


@dataclass
class LocalIndex:
    district_id: int
    vertices: np.ndarray        # (k,) int32 global ids, ascending
    border_locals: np.ndarray   # (b,) int64 positions of borders
    labels: SparseLabels        # L_i⁺ if augmented else L_i (local ids)
    augmented: bool
    # distances from every local vertex to every district border, via the
    # local labels only — precomputed once, powers LB in O(b) per endpoint
    border_dist: np.ndarray = field(default=None)  # type: ignore[assignment]
    # lazily-built dense hub-aligned table (see dense_table); hubs of L_i
    # are local ids, so the hub axis is the district's own vertex range
    _dense: np.ndarray | None = field(default=None, repr=False)

    def __post_init__(self):
        if self.border_dist is None:
            # λ(v, b) for every (v, border) pair at once: a min-plus
            # product over the hub-aligned table (hubs are local ids), the
            # same float32 sums and minima as one query_many per border
            dense = self.labels.to_dense_hub_table(self.labels.num_vertices)
            self.border_dist = minplus(dense, dense[self.border_locals].T)

    def local_of(self, global_ids: np.ndarray) -> np.ndarray:
        return np.searchsorted(self.vertices, global_ids)

    def query_local(self, s_local: int, t_local: int) -> float:
        return self.labels.query(s_local, t_local)

    def dense_table(self) -> np.ndarray:
        """Hub-aligned dense layout of the local labels: ``(k, k)`` float32
        with ``table[v, h] = λ-entry dist(v, h)`` and +inf where ``h`` is
        not a hub of ``v`` — the same TPU serving layout as BorderLabels
        (slot j ≡ local vertex j), so same-district joins run through the
        identical dense ``label_join`` kernel as rule-3. Built once per
        index version and cached; O(k²) floats is the price of keeping the
        serving join O(k) instead of the sparse O(L²) mask."""
        if self._dense is None:
            self._dense = self.labels.to_dense_hub_table(
                self.labels.num_vertices)
        return self._dense

    def query_local_many(self, s_locals: np.ndarray, t_locals: np.ndarray,
                         use_kernels: bool = True) -> np.ndarray:
        """Vectorized λ(s,t,L_i) for a bucket of same-district queries
        (local ids). Routed through the dense label_join kernel over the
        hub-aligned table by default."""
        if use_kernels:
            from ..kernels.label_join import ops as lj
            return lj.join_gathered(self.dense_table(), s_locals, t_locals)
        return self.labels.query_many(s_locals, t_locals)

    def local_bound_many(self, s_locals: np.ndarray, t_locals: np.ndarray,
                         use_kernels: bool = True) -> np.ndarray:
        """Vectorized Definition-5 Local Bound over the precomputed
        vertex→border distance table."""
        if use_kernels:
            from ..kernels.label_join import ops as lj
            return lj.bound_gathered(self.border_dist, s_locals, t_locals)
        if len(self.border_locals) == 0:
            return np.full(len(s_locals), INF, dtype=np.float32)
        return (self.border_dist[s_locals].min(axis=1)
                + self.border_dist[t_locals].min(axis=1)).astype(np.float32)

    def size_bytes(self) -> int:
        return self.labels.size_bytes()


def build_local_index(g: Graph, part: Partition, district_id: int,
                      bl: BorderLabels | None = None) -> LocalIndex:
    """Build L_i (bl=None) or L_i⁺ (bl given → shortcuts folded in)."""
    vertices = np.nonzero(part.assignment == np.int32(district_id))[0] \
        .astype(np.int32)
    district_borders = borders_of(g, part)[district_id]
    pos = {int(v): i for i, v in enumerate(vertices)}
    border_locals = np.array([pos[int(b)] for b in district_borders],
                             dtype=np.int64)
    extra = None
    if bl is not None and len(district_borders) > 1:
        sc = border_shortcut_matrix(bl, district_borders)
        extra = shortcut_edges(border_locals, sc)
    labels, verts = pll_subgraph(g, vertices, extra_edges=extra)
    return LocalIndex(district_id, verts, border_locals, labels,
                      augmented=bl is not None)


def build_all_local_indexes(g: Graph, part: Partition,
                            bl: BorderLabels | None = None
                            ) -> list[LocalIndex]:
    return [build_local_index(g, part, i, bl=bl)
            for i in range(part.num_districts)]
