"""Steady-state batched serving engine: one device dispatch per batch.

The per-bucket route (gather on host, one kernel call per district) pays
a host→device copy and a dispatch per bucket — dozens of round trips per
batch. This engine instead answers the whole batch with a single jitted
gather→join over ONE combined label table, the EdgeLake-style
consolidation shape: transform the batch once on the host (pure NumPy
routing → row ids), then a single fan-out/reduce on device.

Layout: the m district tables L_i⁺ — each densified to the hub-aligned
``(k_i, k_i)`` form (slot j ≡ local vertex j, the same §5.1 layout
BorderLabels uses) — are stacked on top of the border table B, all
inf-padded to a common hub width W = max(kmax, q):

    row of vertex v for a rule-1/2 query = d(v)·kmax + local(v)
    row of vertex v for a rule-3  query = m·kmax + v

Because a 2-hop join over inf-padded rows ignores the padding lanes, one
``label_join.join`` call answers every routing rule at once; the engine
never branches on rule. The result is already consolidated — the row-id
transform IS the scatter.

The engine is a snapshot of one index version: the router rebuilds it
(cheap: one densify pass per district) whenever the center pushes new
shortcuts, and falls back to the bucketed Theorem-3 path while any
district's L_i⁺ is stale.

Paper map: the row-id transform implements the §4.2 query rules (rule
1/2 → district rows, rule 3 → border rows of B); the dense join is
Definition 1 on the hub-aligned §5.1 layout; the rebuild-window fallback
(in ``edge/router.py``) is the Theorem-3 Local-Bound certificate. Three
engine layouts trade memory for collectives — replicated
(``BatchedQueryEngine``), district-sharded, and fully-sharded
(``ShardedBatchedEngine`` with ``shard_border=True``); see
docs/ARCHITECTURE.md for the memory model and README "Choosing an
engine" for how the router auto-picks.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import obs
from ..core.local_index import LocalIndex
from ..core.quantize import QuantSpec
from ..kernels.label_join import ops as lj
from .sharded_oracle import (default_edge_mesh, make_sharded_query_fn,
                             pack_tables, prepare_queries)

INF = np.float32(np.inf)


# Module-level jit: the compile cache is keyed on shapes + use_pallas, so
# rebuilding the engine after a traffic update (new table values, same
# shapes) reuses the compiled program instead of re-tracing every epoch.
@functools.partial(jax.jit, static_argnames="use_pallas")
def _engine_fn(table, rs, rt, use_pallas: bool):
    return lj.join(table[rs], table[rt], use_pallas=use_pallas)


# Quantized twin: the table holds core.quantize codes; (sentinel, scale)
# are static so the compiled program bakes the widening constants in.
@functools.partial(jax.jit,
                   static_argnames=("use_pallas", "sentinel", "scale"))
def _engine_fn_quantized(table, rs, rt, use_pallas: bool,
                         sentinel: int, scale: float):
    return lj.join_quantized(table[rs], table[rt], sentinel=sentinel,
                             scale=scale, use_pallas=use_pallas)


def _bucket(qn: int) -> int:
    """The length a batch of ``qn`` pairs is padded to: the next multiple
    of PAD_Q, so the jit only ever sees a bounded set of shapes (routing
    writes zeros into the padding lanes, which join row 0 against itself
    — on device 0, for the sharded engine — and are sliced off)."""
    qp = lj._ceil_to(qn, lj.PAD_Q)
    obs.count("serve.pad_pairs", qp - qn)
    return qp


class BatchedQueryEngine:
    """Vectorized §4.2 serving over a fixed index version.

    ``quant`` stores the combined table as ``core.quantize`` codes
    (half the resident bytes; bit-for-bit answers for a lossless
    spec)."""

    def __init__(self, btable: np.ndarray, locals_: list[LocalIndex],
                 assignment: np.ndarray, use_pallas: bool | None = None,
                 quant: QuantSpec | None = None):
        # single-shard blocked packing == the combined replicated layout:
        # district rows d·kmax + local(v), then B at rows m·kmax + v
        self.data = pack_tables(btable, locals_, assignment, num_devices=1,
                                combined=True, quant=quant)
        self.quant = quant
        self._table = jnp.asarray(self.data.combined_table)
        self.data.release_host_tables()     # device copy is authoritative
        if use_pallas is None:          # Pallas kernel on accelerators,
            use_pallas = jax.default_backend() != "cpu"   # XLA ref on CPU
        self.use_pallas = use_pallas

    def size_bytes(self) -> int:
        return int(self._table.size * self._table.dtype.itemsize)

    def row_ids(self, ss: np.ndarray, ts: np.ndarray
                ) -> tuple[np.ndarray, np.ndarray]:
        """Host-side batch transform: §4.2 routing collapsed into combined-
        table row ids (the one-shard case of the mesh routing pass —
        every query is 'owned' by device 0), int32 and padded to the
        PAD_Q bucket the jitted step takes (see _bucket)."""
        q = prepare_queries(self.data, ss, ts, _bucket(len(ss)))
        return q["rs"], q["rt"]

    def query(self, ss: np.ndarray, ts: np.ndarray) -> np.ndarray:
        """Answer a batch (padded to a PAD_Q bucket, see _bucket)."""
        ss = np.asarray(ss, dtype=np.int64)
        ts = np.asarray(ts, dtype=np.int64)
        qn = len(ss)
        if qn == 0:
            return np.zeros(0, dtype=np.float32)
        with obs.span("repro.route"):
            rs, rt = self.row_ids(ss, ts)
        with obs.span("repro.dispatch"):
            if self.quant is None:
                out = _engine_fn(self._table, rs, rt,
                                 use_pallas=self.use_pallas)
            else:
                if self.use_pallas:     # the kernel widens the codes
                    obs.count("kernel.join_codes")
                sent, scale = self.quant.key()
                out = _engine_fn_quantized(self._table, rs, rt,
                                           use_pallas=self.use_pallas,
                                           sentinel=sent, scale=scale)
        with obs.span("repro.fetch"):
            return np.asarray(out)[:qn]

    __call__ = query
    # QueryPlane conformance: the engine snapshot is the steady-state
    # execution plane of serve.service.DistanceService
    execute = query


class ShardedBatchedEngine:
    """Mesh-sharded §4.2 serving: the combined table split over the
    ``edge`` axis instead of replicated.

    Same contract as ``BatchedQueryEngine.query`` (bit-for-bit identical
    answers) but each device holds only its blocked slice of the district
    tables — ``ceil(m/E)`` districts, ~1/E of the replicated engine's
    district footprint — plus either the whole border table B at its
    natural width q (default) or, with ``shard_border=True``, only a
    ``ceil(n/E)`` row-slice of it, retiring the last replicated
    structure in the serving path. The host routing pass emits
    (owner, row) coordinates and one collective dispatch (per-device
    ``label_join`` gather-join + ``pmin`` over the axis; the B-sharded
    mode assembles the touched B rows with a ragged gather + ``pmin``
    first) answers the whole mixed-rule batch. See
    ``edge.sharded_oracle`` for the layout and device function.
    """

    def __init__(self, btable: np.ndarray, locals_: list[LocalIndex],
                 assignment: np.ndarray, mesh: Mesh | None = None,
                 axis: str = "edge", use_pallas: bool | None = None,
                 shard_border: bool = False,
                 quant: QuantSpec | None = None,
                 placement: np.ndarray | None = None):
        if mesh is None:
            mesh = default_edge_mesh(axis=axis)
        self.mesh = mesh
        self.axis = axis
        self.num_devices = mesh.shape[axis]
        self.shard_border = shard_border
        self.quant = quant
        # placement = explicit district → device table (the online
        # repartitioner's routing table); None = blocked default.  The
        # pack pass memcpys each district's CACHED dense table into its
        # slot, so a migration re-densifies nothing — only the moved
        # districts change coordinates.
        self.data = pack_tables(btable, locals_, assignment,
                                self.num_devices,
                                shard_border=shard_border, quant=quant,
                                placement=placement)
        if use_pallas is None:
            use_pallas = jax.default_backend() != "cpu"
        self.use_pallas = use_pallas
        self._fn = make_sharded_query_fn(
            mesh, axis, use_pallas, shard_border=shard_border,
            quant=quant.key() if quant is not None else None)
        self._table = jax.device_put(self.data.district_table,
                                     NamedSharding(mesh, P(axis)))
        bspec = P(self.axis) if shard_border else P()
        self._btable = jax.device_put(self.data.btable,
                                      NamedSharding(mesh, bspec))
        # the full combined table must not stay resident on the host —
        # per-engine footprint ~1/E is the point of sharding
        self.data.release_host_tables()

    def district_table_bytes_per_device(self) -> int:
        return self.data.district_bytes_per_device()

    def border_table_bytes_per_device(self) -> int:
        """Resident bytes of B on each device: ``n·q`` entries
        replicated, ``ceil(n/E)·q`` row-sharded, times the storage
        itemsize (4 float32, 2 quantized)."""
        return self.data.border_bytes_per_device()

    def size_bytes(self) -> int:
        """Per-device resident bytes (district block + B share)."""
        return self.data.bytes_per_device()

    def row_ids(self, ss: np.ndarray, ts: np.ndarray
                ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Host routing pass → (owner device, per-device s row, t row),
        int32 and padded to the PAD_Q bucket (see _bucket)."""
        q = prepare_queries(self.data, ss, ts, _bucket(len(ss)))
        return q["owner"], q["rs"], q["rt"]

    def _count(self, owner: np.ndarray, rs: np.ndarray) -> None:
        """What the mesh adds to a call, over its real lanes: the most
        lanes one device owns, and with B row-sharded the B rows the
        ragged assembly brings (two per cross-district lane)."""
        obs.count("shard.max_owner_lanes",
                  int(np.bincount(owner, minlength=self.num_devices).max()))
        if self.shard_border:
            obs.count("shard.border_rows",
                      2 * int((rs >= self.data.cross_base).sum()))

    def query(self, ss: np.ndarray, ts: np.ndarray) -> np.ndarray:
        """Answer a batch (padded to a PAD_Q bucket exactly like the
        replicated engine, see _bucket)."""
        ss = np.asarray(ss, dtype=np.int64)
        ts = np.asarray(ts, dtype=np.int64)
        qn = len(ss)
        if qn == 0:
            return np.zeros(0, dtype=np.float32)
        with obs.span("repro.route"):
            owner, rs, rt = self.row_ids(ss, ts)
            self._count(owner[:qn], rs[:qn])
        with obs.span("repro.dispatch"):
            if self.use_pallas and self.quant is not None:
                obs.count("kernel.join_codes")
            out = self._fn(self._table, self._btable, owner, rs, rt)
        with obs.span("repro.fetch"):
            return np.asarray(out)[:qn]

    __call__ = query
    # QueryPlane conformance (see BatchedQueryEngine)
    execute = query
