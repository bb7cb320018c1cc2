"""Districts → devices: the edge deployment mapped onto a JAX mesh.

Every device of the ``edge`` mesh axis plays the role of a group of edge
servers: it owns a *blocked* slice of the combined hub-aligned district
tables — ``dpd = ceil(m / E)`` districts per device, every district
densified to the same ``(kmax, W)`` layout the replicated
``BatchedQueryEngine`` uses — plus the border-label table B (the
computing center) in one of two placements:

* **replicated** (default): every device holds all n rows of B at its
  natural width q (NOT padded to W — the gathered rows are padded
  per-batch inside ``join_sharded_gathered``), so rule-3 queries cost
  zero extra collectives;
* **row-sharded** (``shard_border=True``): each device holds only a
  ``ceil(n/E)`` row-slice of B, and the batched join assembles the
  touched rows with a ragged gather + ``pmin``
  (``join_sharded_border_gathered``). Nothing in the serving path is
  replicated anymore — per-device bytes fall from
  ``dpd·kmax·W·4 + n·q·4`` to ``dpd·kmax·W·4 + ceil(n/E)·q·4``.

This is how a label store scales past a single device's memory: every
structure is partitioned, so the per-device footprint is ~1/E of the
full index.

A query batch is preprocessed on the host into (owner, row) coordinates,
read from per-vertex int32 columns that packing builds once:

  rule 1/2 — owner = the device holding district d (blocked assignment
             ``d // dpd``), row = the query endpoint's slot in that
             device's table block (``(d % dpd)·kmax + local``);
  rule 3   — owner = the device holding the *source* district (load-
             balanced center), row = the vertex's row in the replicated B
             (offset past the device's district block);

then ONE collective dispatch answers the whole mixed-rule batch: each
device concatenates [its district block; B], runs the same dense
``label_join`` gather-join the replicated engine runs, masks lanes it
does not own to +inf, and a single ``pmin`` over the axis assembles the
answer vector. This is the §4.2 routing with collectives instead of
RPCs; the same function runs on 1 device (tests), 8 host devices
(integration test + CI), or a pod axis.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..core.labels import BorderLabels
from ..core.local_index import LocalIndex
from ..core.partition import Partition
from ..core.quantize import QuantSpec
from ..kernels.label_join import ops as lj

INF = np.float32(np.inf)


@dataclass
class ShardedOracleData:
    """Host-packed blocked layout. ``district_table`` rows are grouped by
    district (``kmax`` rows each) so slicing the leading axis into E equal
    chunks hands device d exactly districts ``d·dpd .. d·dpd+dpd-1``.
    ``btable`` is stored at its NATURAL width q (not the combined W)
    except in the ``combined=True`` single-buffer layout; with
    ``border_sharded`` its rows are padded to ``ceil(n/E)·E`` so the
    leading axis shards evenly over the mesh too."""
    district_table: np.ndarray | None  # (m_pad·kmax, W) — shardable
    btable: np.ndarray | None   # (n_pad, q) — center table B
    # per-vertex routing columns, int32 (4 B a vertex each), built once
    # at pack time from the district → (device, slot) placement; routing
    # state, so they survive release_host_tables
    district: np.ndarray        # (n,) vertex → its district
    home: np.ndarray            # (n,) vertex → its rule-1/2 row, in its
    #                             owner's block: slot·kmax + local slot
    owner: np.ndarray           # (n,) vertex → device holding its district
    kmax: int
    num_devices: int
    num_districts: int
    # layout scalars snapshotted at pack time so the big host arrays can
    # be released once the tables are device-resident (routing and the
    # bytes accounting never touch the arrays again)
    districts_per_device: int = field(init=False)
    width: int = field(init=False)
    border_width: int = field(init=False)
    border_rows_per_device: int = field(init=False)
    num_vertices: int = field(init=False)
    itemsize: int = field(init=False)
    # single-allocation [districts; B] buffer (combined=True packing);
    # district_table/btable are views into it — the replicated engine
    # ships this to the device without a second host copy
    combined_table: np.ndarray | None = None
    # True ⇒ btable is a row-sharded (n_pad, q) layout: device d owns
    # rows d·rpd .. d·rpd+rpd-1 (rpd = ceil(n/E))
    border_sharded: bool = False
    # set ⇒ tables hold quantized integer codes (core.quantize); the
    # device joins are handed quant.key() and answers stay float32
    quant: QuantSpec | None = None

    def __post_init__(self):
        self.districts_per_device = (self.district_table.shape[0]
                                     // self.kmax // self.num_devices)
        self.width = self.district_table.shape[1]
        self.border_width = self.btable.shape[1]
        self.border_rows_per_device = (
            self.btable.shape[0] // self.num_devices
            if self.border_sharded else self.btable.shape[0])
        self.num_vertices = len(self.district)
        self.itemsize = int(self.district_table.dtype.itemsize)

    @property
    def cross_base(self) -> int:
        """Per-device row offset of B inside [district block; B]."""
        return self.districts_per_device * self.kmax

    def release_host_tables(self) -> None:
        """Drop the packed host copies (an engine calls this after
        ``device_put`` — keeping them would hold the FULL combined table
        in host RAM per engine instance, which is exactly the footprint
        sharding exists to avoid)."""
        self.district_table = None
        self.btable = None
        self.combined_table = None

    def district_bytes_per_device(self) -> int:
        return (self.districts_per_device * self.kmax * self.width
                * self.itemsize)

    def border_bytes_per_device(self) -> int:
        """Resident bytes of B per device: all ``n·q`` entries when
        replicated (natural width), a ``ceil(n/E)·q`` row-slice when
        sharded — times the storage itemsize (4 for float32, 2
        quantized)."""
        return (self.border_rows_per_device * self.border_width
                * self.itemsize)

    def bytes_per_device(self) -> int:
        """Resident bytes per device: district block + this device's
        share of B (see the memory model in docs/ARCHITECTURE.md)."""
        return (self.district_bytes_per_device()
                + self.border_bytes_per_device())


def pack_tables(btable: np.ndarray, locals_: list[LocalIndex],
                assignment: np.ndarray, num_devices: int, *,
                combined: bool = False,
                shard_border: bool = False,
                quant: QuantSpec | None = None,
                placement: np.ndarray | None = None) -> ShardedOracleData:
    """Blocked packing of the combined hub-aligned table: districts padded
    to ``m_pad = dpd·E`` so the leading axis shards evenly, every district
    table densified to (kmax, W) with the same inf padding the replicated
    engine uses (padding lanes never win a min-plus join).

    B is kept at its natural width q: the device join pads the few
    *gathered* rows per batch to W instead of storing ``n·(W−q)`` dead
    lanes. ``shard_border=True`` additionally row-pads B to
    ``n_pad = rpd·E`` so it shards evenly over the mesh (device d owns
    rows ``d·rpd .. d·rpd+rpd-1``).

    ``combined=True`` lays districts and B out in ONE allocation (the
    replicated engine's device layout, B padded to W there) so no second
    host copy is needed to stack them; ``district_table``/``btable``
    become views.

    ``quant`` switches the storage dtype: tables hold ``core.quantize``
    codes (2 bytes/entry) and every padding element is the dtype's
    sentinel — the quantized image of +inf, so padding lanes still
    never win the join.

    ``placement`` is an explicit district → device table (the
    repartitioner's ``EdgePlacement.host_of`` with one host per device);
    each device's resident districts are packed into its slots
    ``0..count-1`` and the block height becomes the *maximum* per-device
    district count.  ``None`` keeps the blocked default (district i on
    device ``i // dpd`` at slot ``i % dpd``).

    The placement is folded into the per-vertex int32 routing columns
    (``district``, ``home``, ``owner``) that ``prepare_queries`` reads;
    every row id must fit int32, so packing raises when
    ``dpd·kmax + n`` (one past the last row of B) reaches 2**31."""
    assert not (combined and shard_border), \
        "combined packing keeps B inside the single replicated buffer"
    n = len(assignment)
    m = len(locals_)
    if placement is None:
        dpd = -(-m // num_devices)
        ids = np.arange(m, dtype=np.int64)
        device_of, slot_of = ids // dpd, ids % dpd
    else:
        device_of = np.asarray(placement, dtype=np.int64)
        if device_of.shape != (m,):
            raise ValueError(f"placement must map all {m} districts")
        if len(device_of) and (device_of.min() < 0
                               or device_of.max() >= num_devices):
            raise ValueError("placement host ids must lie in "
                             f"[0, {num_devices})")
        counts = np.bincount(device_of, minlength=num_devices)
        dpd = max(1, int(counts.max()))
        slot_of = np.zeros(m, dtype=np.int64)
        for dev in range(num_devices):
            resident = np.nonzero(device_of == dev)[0]
            slot_of[resident] = np.arange(len(resident))
    m_pad = dpd * num_devices
    kmax = max(len(li.vertices) for li in locals_)
    if dpd * kmax + n >= 2**31:
        raise ValueError(f"row ids up to {dpd * kmax + n} do not fit int32")
    q = btable.shape[1]
    width = max(kmax, q, 1)
    rows = m_pad * kmax
    if quant is None:
        dtype, fill = np.dtype(np.float32), INF
        enc = lambda a: np.asarray(a, dtype=np.float32)  # noqa: E731
    else:
        dtype, fill = quant.dtype, quant.dtype.type(quant.sentinel)
        enc = quant.quantize
    if combined:
        buf = np.full((rows + n, width), fill, dtype=dtype)
        table, bt = buf[:rows], buf[rows:]
        bt[:, :q] = enc(btable)
    else:
        buf = None
        table = np.full((rows, width), fill, dtype=dtype)
        if shard_border:
            n_pad = -(-n // num_devices) * num_devices
            bt = np.empty((n_pad, q), dtype=dtype)
            bt[:n] = enc(btable)
            bt[n:] = fill
        elif quant is None:
            # zero-copy when the caller's B is already f32-contiguous:
            # pack never mutates it and the engines device_put + release
            bt = np.ascontiguousarray(btable, dtype=np.float32)
        else:
            bt = enc(btable)
    local_pos = np.zeros(n, dtype=np.int64)
    for i, li in enumerate(locals_):
        k = len(li.vertices)
        base = (device_of[i] * dpd + slot_of[i]) * kmax
        table[base:base + k, :k] = enc(li.dense_table())
        local_pos[li.vertices] = np.arange(k, dtype=np.int64)
    district = np.asarray(assignment).astype(np.int32)
    home = (slot_of[district] * kmax + local_pos).astype(np.int32)
    owner = device_of[district].astype(np.int32)
    return ShardedOracleData(table, bt, district, home, owner, kmax,
                             num_devices, m, combined_table=buf,
                             border_sharded=shard_border, quant=quant)


def pack_for_mesh(part: Partition, bl: BorderLabels,
                  locals_: list[LocalIndex], num_devices: int, *,
                  shard_border: bool = False,
                  quant: QuantSpec | None = None) -> ShardedOracleData:
    """Paper-facing wrapper: pack a built index for an E-device edge mesh."""
    return pack_tables(bl.table.astype(np.float32), locals_,
                       part.assignment, num_devices,
                       shard_border=shard_border, quant=quant)


def prepare_queries(data: ShardedOracleData, ss: np.ndarray,
                    ts: np.ndarray, size: int | None = None
                    ) -> dict[str, np.ndarray]:
    """Host-side client/edge-server routing pass: each query's owning
    device and the two per-device row ids its gather-join reads (§4.2
    rules collapsed into coordinates), int32, from the per-vertex
    columns. A lane whose endpoints share a district (rule 1/2) reads
    both ``home`` rows on the device of that district; any other lane
    (rule 3) reads ``cross_base + v`` for both endpoints, their rows of
    B, on the device of the source's district. ``size`` (default: the
    batch length) is the length of the returned arrays: lanes past the
    batch are zero, the padding a jitted step's bucket takes."""
    ss = np.asarray(ss)
    ts = np.asarray(ts)
    qn = len(ss)
    size = qn if size is None else size
    local = np.flatnonzero(data.district.take(ss) == data.district.take(ts))
    out = {}
    for key, v in (("rs", ss), ("rt", ts)):
        rows = np.zeros(size, dtype=np.int32)
        np.add(v, data.cross_base, out=rows[:qn], casting="unsafe")
        rows[local] = data.home.take(v.take(local))
        out[key] = rows
    out["owner"] = np.zeros(size, dtype=np.int32)
    if data.num_devices > 1:        # on one device every lane is its own
        out["owner"][:qn] = data.owner.take(ss)
    return out


_FN_CACHE: dict = {}


def make_sharded_query_fn(mesh: Mesh, axis: str = "edge",
                          use_pallas: bool = False,
                          shard_border: bool = False,
                          quant: tuple[int, float] | None = None):
    """Jitted ``fn(district_block, btable, owner, rs, rt)`` bound to
    ``mesh`` (jit name ``_sharded_step``): per-device dense gather-join
    over [block; B] + one pmin.
    With ``shard_border`` the btable argument is the row-sharded B and
    the touched rows are assembled by ragged gather + pmin first.
    ``quant`` is a ``QuantSpec.key()`` pair when the tables hold
    quantized codes. Cached per (mesh, axis, use_pallas, shard_border,
    quant) so engine rebuilds after traffic updates reuse the compiled
    program."""
    key = (mesh, axis, use_pallas, shard_border, quant)
    if key in _FN_CACHE:
        return _FN_CACHE[key]

    # the jit takes the mapped function's name: the compiled module is
    # ``jit__sharded_step``, which a profiler trace lists under that name
    if shard_border:
        def _sharded_step(table, bshard, owner, rs, rt):
            return lj.join_sharded_border_gathered(
                table, bshard, owner, rs, rt,
                axis=axis, use_pallas=use_pallas, quant=quant)
    else:
        def _sharded_step(table, btable, owner, rs, rt):
            return lj.join_sharded_gathered(table, btable, owner, rs, rt,
                                            axis=axis,
                                            use_pallas=use_pallas,
                                            quant=quant)

    # check_vma=False: the Pallas join's out_shape carries no varying-
    # manual-axes annotation, which the checker requires of every value
    # inside the map; the pmin at the end already makes the output
    # replicated, as out_specs=P() states
    sharded = jax.shard_map(
        _sharded_step, mesh=mesh,
        in_specs=(P(axis), P(axis) if shard_border else P(),
                  P(), P(), P()),
        out_specs=P(), check_vma=False,
    )
    fn = jax.jit(sharded)
    _FN_CACHE[key] = fn
    return fn


@functools.lru_cache(maxsize=None)
def _mesh_cache(num_devices: int, axis: str) -> Mesh:
    return Mesh(np.array(jax.devices()[:num_devices]).reshape(num_devices),
                (axis,))


def default_edge_mesh(num_devices: int | None = None,
                      axis: str = "edge") -> Mesh:
    """1-D ``edge`` mesh over the backend's devices (cached: the same Mesh
    object comes back so jit caches keyed on it stay warm)."""
    ndev = len(jax.devices()) if num_devices is None else num_devices
    return _mesh_cache(ndev, axis)


def sharded_query(data: ShardedOracleData, mesh: Mesh,
                  queries: dict[str, np.ndarray], axis: str = "edge",
                  use_pallas: bool | None = None) -> np.ndarray:
    """One-shot deployment entry point (tests / notebooks): place the
    packed tables on the mesh and answer one prepared batch. Serving hot
    paths should hold a ``ShardedBatchedEngine`` instead, which keeps the
    tables device-resident across batches."""
    if use_pallas is None:
        use_pallas = jax.default_backend() != "cpu"
    fn = make_sharded_query_fn(
        mesh, axis, use_pallas, shard_border=data.border_sharded,
        quant=data.quant.key() if data.quant is not None else None)
    dev_sharding = NamedSharding(mesh, P(axis))
    rep = NamedSharding(mesh, P())
    table = jax.device_put(data.district_table, dev_sharding)
    btable = jax.device_put(data.btable,
                            dev_sharding if data.border_sharded else rep)
    q = {k: jax.device_put(jnp.asarray(queries[k]), rep)
         for k in ("owner", "rs", "rt")}
    return np.asarray(fn(table, btable, q["owner"], q["rs"], q["rt"]))
