"""Scatter-gather read path: cross-edge serving with the center retired.

The engines in ``edge/engine.py`` model the deployment as one device
mesh; this module models it as the paper's §4 *network* — m autonomous
edge servers and a coordinator — while answering bit-for-bit the same
distances.  A mixed-rule batch is split by the coordinator into one
partial query per district (the EdgeLake remote/local query rewriting,
SNIPPETS.md #1):

* rule 1/2 lanes go to the district's own server, which joins over its
  hub-aligned L_i⁺ block;
* rule 3 lanes go to the *source* district's server, which joins the
  source vertex's own B row against the target vertex's B row — a row it
  obtained from the target district's server through the peer-to-peer
  border-row exchange (``EdgeServer.exchange_border_rows``), never from
  the center.  The §4.2 rule-3 identity ``d(s,t) = min_b B[s,b] +
  B[t,b]`` needs nothing else, so the computing center leaves the read
  path entirely: it builds B and pushes each district its slice
  (``ComputingCenter.border_rows_for``), then every query is answered
  edge-side over ``peer_edge_ms`` links instead of two WAN hops.

Each server's partial is a full-batch vector holding its answers on the
lanes it owns and +inf elsewhere; the coordinator consolidates with ONE
element-wise min over the m partials — MIN-of-MINs, the host-side
analogue of the sharded engine's ``pmin``.  Because every lane is owned
by exactly one server, the rows each partial joins are identical to the
rows the sharded engine's owning device joins (same ``pack_tables``
densify, same natural-width-q border rows inf-padded to W, same
``label_join`` kernel), so the plane is bit-for-bit with
``ShardedBatchedEngine`` — pinned in ``tests/test_scatter_gather.py``
on 1 and 8 virtual devices.

The plane implements the ``QueryPlane`` protocol; select it with
``ServingPolicy(engine="scatter_gather")``.  Latency consequences are
modeled in ``edge/simulator.py`` and ``serve/loadgen.py`` (cross-district
requests pay ``Topology.peer_rtt_ms()`` instead of ``forward_rtt_ms()``)
and measured in ``benchmarks/bench_scatter.py``.

**Faults** (``edge/faults.py``): with ``ServingPolicy(faults=...)`` the
plane runs every peer exchange through a deterministic ``FaultInjector``
and degrades instead of erroring — bounded retry + backoff on the link,
(s, t)-swap reroute to the surviving district's server when the owner is
dark (bit-identical by min symmetry), forwarded-path fallback through
the center (exact for rule-3 lanes), previous-generation border rows
(flagged ``stale``), and finally a flagged +inf.  After a faulted batch
the plane's ``exactness_codes`` / ``degraded`` arrays carry the
per-lane verdict into ``ResultBatch`` — no silent wrong answers.  With
the plan disabled the fault path is never entered and the plane stays
bit-for-bit with the engines.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import TYPE_CHECKING

import jax
import numpy as np

from ..core.local_index import LocalIndex
from ..core.quantize import QuantSpec
from ..kernels.label_join import ops as lj
from .server import EdgeServer
from .sharded_oracle import pack_tables, prepare_queries

if TYPE_CHECKING:                                   # pragma: no cover
    from .router import EdgeSystem

INF = np.float32(np.inf)


@dataclass
class ScatterGatherPlane:
    """Coordinator + per-district partial execution over the servers'
    own label stores.  A snapshot of one index version, like the
    engines; the router rebuilds it when the center's version moves."""
    servers: list[EdgeServer]
    version: int
    use_pallas: bool
    data: object                        # ShardedOracleData, num_devices=m
    border_width: int
    # per-server dense view of the border rows it holds, scattered in as
    # slices arrive (own push + peer exchanges); lazily allocated so
    # servers that never see a cross lane hold no B bytes at all
    _bviews: list[np.ndarray | None] = field(repr=False)
    _held: list[set] = field(repr=False)
    exchange_stats: dict = field(default_factory=lambda: {
        "exchanges": 0, "rows_exchanged": 0, "retries": 0,
        "failed_exchanges": 0, "charged_ms": 0.0, "co_hosted_rows": 0})
    # district → edge-host routing table (repro.topo.EdgePlacement, set
    # by the router from EdgeSystem.placement).  Districts sharing a
    # host exchange border rows over loopback: the copy still happens,
    # but it is counted as co_hosted_rows instead of a peer-link
    # exchange and (in the faulted path) no link fault can apply.
    placement: object | None = field(default=None, repr=False)
    # fault-injection runtime (edge/faults.FaultInjector) — None on the
    # clean fast path, which then stays bit-for-bit with the engines
    faults: object | None = field(default=None, repr=False)
    # forwarded-path fallback target (ComputingCenter); only read when
    # degrading — the clean read path never touches it
    center: object | None = field(default=None, repr=False)
    # districts whose rows in a server's view are previous-generation
    _stale_held: list[set] = field(default_factory=list, repr=False)
    # per-batch degradation metadata (None after a clean batch); the
    # request plane lifts these into ResultBatch via getattr
    exactness_codes: np.ndarray | None = field(default=None, repr=False)
    degraded: np.ndarray | None = field(default=None, repr=False)
    # set ⇒ the district block and the per-server border views hold
    # core.quantize codes (2 bytes/entry on every host); rows are
    # dequantized per batch in _gather, so a lossless spec keeps the
    # plane bit-for-bit with the engines
    quant: QuantSpec | None = field(default=None, repr=False)

    def __post_init__(self):
        if not self._stale_held:
            self._stale_held = [set() for _ in self.servers]

    @classmethod
    def from_system(cls, system: "EdgeSystem",
                    use_pallas: bool | None = None,
                    faults=None,
                    quant: QuantSpec | None = None
                    ) -> "ScatterGatherPlane":
        """Build from a deployed system: the center pushes each server
        its own district's B rows (the build-path role it keeps), then
        the coordinator packs the same blocked layout the sharded engine
        uses — one 'device' per district, so the routing pass emits
        per-district row coordinates directly."""
        center = system.center
        version = center.version
        for srv in system.servers:
            if not srv.has_border_rows(srv.district_id, version):
                verts, rows = center.border_rows_for(srv.district_id)
                srv.install_border_rows(verts, rows, version)
        plane = cls.build(center.border_labels.table,
                          [srv.augmented for srv in system.servers],
                          system.partition.assignment, system.servers,
                          version, use_pallas=use_pallas, quant=quant)
        plane.center = center
        plane.placement = system.placement
        if faults is not None and getattr(faults, "enabled", False):
            from .faults import FaultInjector
            plane.faults = FaultInjector(faults)
        return plane

    @classmethod
    def build(cls, btable: np.ndarray, locals_: list[LocalIndex],
              assignment: np.ndarray, servers: list[EdgeServer],
              version: int,
              use_pallas: bool | None = None,
              quant: QuantSpec | None = None) -> "ScatterGatherPlane":
        m = len(locals_)
        data = pack_tables(btable, locals_, assignment, num_devices=m,
                           quant=quant)
        q = data.border_width
        # the coordinator holds NO border rows — rule-3 gathers read the
        # servers' exchanged stores, so drop the packed full-B copy
        data.btable = None
        return cls(servers, version,
                   (jax.default_backend() != "cpu"
                    if use_pallas is None else use_pallas),
                   data, q, [None] * m, [set() for _ in range(m)],
                   quant=quant)

    # -- border-row assembly -------------------------------------------------

    def _bview(self, d: int) -> np.ndarray:
        if self._bviews[d] is None:
            if self.quant is None:
                self._bviews[d] = np.full(
                    (self.data.num_vertices, self.border_width), INF,
                    dtype=np.float32)
            else:
                self._bviews[d] = np.full(
                    (self.data.num_vertices, self.border_width),
                    self.quant.sentinel, dtype=self.quant.dtype)
        return self._bviews[d]

    def _install_rows(self, d: int, verts: np.ndarray,
                      rows: np.ndarray) -> None:
        """Scatter exchanged float32 B rows into server ``d``'s view
        (quantizing on arrival when the plane stores codes)."""
        if self.quant is not None:
            rows = self.quant.quantize(rows)
        self._bview(d)[verts] = rows

    def _co_hosted(self, d: int, j: int) -> bool:
        p = self.placement
        return p is not None and bool(p.host_of[d] == p.host_of[j])

    def _ensure_rows(self, d: int, districts: np.ndarray) -> None:
        """Make sure server ``d`` holds the B rows of every district in
        ``districts``, running peer exchanges for the ones it lacks.
        Co-hosted peers (same edge host under the current placement)
        copy over loopback — counted, but not as a peer-link exchange."""
        srv = self.servers[d]
        held = self._held[d]
        for j in np.unique(districts):
            j = int(j)
            if j in held:
                continue
            if j != d:
                moved = srv.exchange_border_rows(self.servers[j])
                if moved:
                    if self._co_hosted(d, j):
                        self.exchange_stats["co_hosted_rows"] += moved
                    else:
                        self.exchange_stats["exchanges"] += 1
                        self.exchange_stats["rows_exchanged"] += moved
            verts, rows = srv.border_rows_of(j)
            self._install_rows(d, verts, rows)
            held.add(j)

    def _gather(self, d: int, rows: np.ndarray) -> np.ndarray:
        """Assemble server ``d``'s (batch, W) join rows: district-block
        rows for local row ids, held border rows (inf-padded from the
        natural width q to W) for the rest — the same per-batch padding
        ``join_sharded_gathered`` applies on device.  A quantized plane
        stores codes and dequantizes the few gathered rows here (exact
        for a lossless spec), so the partial join itself is unchanged."""
        kmax = self.data.kmax
        width = self.data.width
        dec = ((lambda a: a) if self.quant is None
               else self.quant.dequantize)
        block = self.data.district_table[d * kmax:(d + 1) * kmax]
        local = rows < kmax
        out = np.empty((len(rows), width), dtype=np.float32)
        out[local] = dec(block[rows[local]])
        cross = ~local
        if cross.any():
            gid = rows[cross] - kmax
            padded = np.full((int(cross.sum()), width), INF,
                             dtype=np.float32)
            padded[:, :self.border_width] = dec(self._bview(d)[gid])
            out[cross] = padded
        return out

    # -- QueryPlane ----------------------------------------------------------

    def execute(self, ss: np.ndarray, ts: np.ndarray) -> np.ndarray:
        """Scatter the batch into per-district partials, consolidate
        with one MIN-of-MINs.  With a fault injector attached the batch
        runs through the degradation ladder instead (``_execute_faulted``
        — same answers wherever nothing actually fails)."""
        ss = np.asarray(ss, dtype=np.int64)
        ts = np.asarray(ts, dtype=np.int64)
        self.exactness_codes = None     # per-batch metadata: reset so a
        self.degraded = None            # clean batch never leaks flags
        qn = len(ss)
        if qn == 0:
            return np.zeros(0, dtype=np.float32)
        if self.faults is not None:
            return self._execute_faulted(ss, ts)
        coords = prepare_queries(self.data, ss, ts)
        owner, rs, rt = coords["owner"], coords["rs"], coords["rt"]
        kmax = self.data.kmax
        partials = []
        for d in np.unique(owner):
            d = int(d)
            sel = np.nonzero(owner == d)[0]
            rs_d, rt_d = rs[sel], rt[sel]
            cross_t = rt_d >= kmax
            if cross_t.any():
                # a cross lane reads the server's OWN B row on the
                # s-side and the peer district's on the t-side
                self._ensure_rows(d, np.append(
                    self.data.district[rt_d[cross_t] - kmax], d))
            vals = lj.join_partial_gathered(
                self._gather(d, rs_d), self._gather(d, rt_d),
                use_pallas=self.use_pallas)
            partial = np.full(qn, INF, dtype=np.float32)
            partial[sel] = vals
            partials.append(partial)
        return np.minimum.reduce(partials)

    query = execute
    __call__ = execute

    # -- graceful degradation under injected faults --------------------------

    def _ensure_rows_faulted(self, d: int, j: int) -> str:
        """Fault-aware counterpart of ``_ensure_rows`` for ONE peer
        district: make server ``d``'s view hold district ``j``'s B rows
        if any rung of the ladder can supply them.  Returns ``"ok"``
        (current rows present), ``"stale"`` (previous generation
        installed), or the blocking fault (``"drop" | "timeout" |
        "outage"``)."""
        srv = self.servers[d]
        held = self._held[d]
        stale_held = self._stale_held[d]
        if j in held and j not in stale_held:
            return "ok"
        if j == d or srv.has_border_rows(j, srv.border_rows_version):
            # own slice, or already cached server-side: no network hop,
            # so no fault can apply (also how a stale view heals)
            verts, rows = srv.border_rows_of(j)
            self._install_rows(d, verts, rows)
            held.add(j)
            stale_held.discard(j)
            return "ok"
        inj = self.faults
        if self._co_hosted(d, j) and not inj.server_down(j):
            # same edge host: the copy is loopback, no peer link to fault
            moved = srv.exchange_border_rows(self.servers[j])
            if moved:
                self.exchange_stats["co_hosted_rows"] += moved
            verts, rows = srv.border_rows_of(j)
            self._install_rows(d, verts, rows)
            held.add(j)
            stale_held.discard(j)
            return "ok"
        if inj.server_down(j):
            fault = "outage"
        else:
            outc = inj.exchange(srv, self.servers[j])
            st = self.exchange_stats
            st["charged_ms"] += outc.charged_ms
            if outc.ok:
                if outc.moved:
                    st["exchanges"] += 1
                    st["rows_exchanged"] += outc.moved
                verts, rows = srv.border_rows_of(j)
                self._install_rows(d, verts, rows)
                held.add(j)
                stale_held.discard(j)
                return "ok"
            st["failed_exchanges"] += 1
            st["retries"] = inj.stats["retries"]
            fault = outc.fault
        if j not in held:
            stale = srv.stale_border_rows_of(j)
            if stale is not None and \
                    stale[1].shape[1] == self.border_width:
                verts, rows = stale
                self._install_rows(d, verts, rows)
                held.add(j)
                stale_held.add(j)
        return "stale" if j in held else fault

    def _execute_faulted(self, ss: np.ndarray, ts: np.ndarray
                         ) -> np.ndarray:
        """The degradation ladder (module docstring of ``edge.faults``):
        reroute dark owners to the surviving min, retry peer links with
        backoff, forward failures through the center, serve stale rows,
        and flag whatever is left — every non-exact answer carries
        ``exactness_codes == 2`` and a ``degraded`` reason string."""
        inj = self.faults
        inj.tick()
        qn = len(ss)
        kmax = self.data.kmax
        assignment = self.data.district
        out = np.full(qn, INF, dtype=np.float32)
        codes = np.zeros(qn, dtype=np.uint8)
        reasons = np.full(qn, None, dtype=object)
        live = np.ones(qn, dtype=bool)
        coords = prepare_queries(self.data, ss, ts)
        owner = coords["owner"].copy()
        rs, rt = coords["rs"].copy(), coords["rt"].copy()
        center_up = self.center is not None and not inj.center_down()

        def via_center(idx: np.ndarray, fault: str) -> None:
            # forwarded-path fallback: the center's B join is the §4.2
            # rule-3 identity, so cross lanes stay EXACT (the reason
            # records the reroute; exactness does not change)
            out[idx] = np.asarray(
                self.center.answer_cross_many(ss[idx], ts[idx]),
                dtype=np.float32)
            reasons[idx] = f"{fault}:forwarded_via_center"
            live[idx] = False

        def via_bound(idx: np.ndarray, fault: str) -> None:
            # same-district lanes on a dark server: min_b B[s,b]+B[t,b]
            # is a certified UPPER bound (triangle inequality over real
            # border paths) — served, but flagged stale
            out[idx] = np.asarray(
                self.center.answer_cross_many(ss[idx], ts[idx]),
                dtype=np.float32)
            codes[idx] = np.uint8(2)
            reasons[idx] = f"{fault}:border_upper_bound"
            live[idx] = False

        def unavailable(idx: np.ndarray, fault: str) -> None:
            codes[idx] = np.uint8(2)            # +inf, flagged — never
            reasons[idx] = f"{fault}:unavailable"   # a silent answer
            live[idx] = False

        # 1. dark owners: reroute cross lanes to the surviving min ----------
        orig_owner = coords["owner"]
        for d in np.unique(orig_owner):
            d = int(d)
            if not inj.server_down(d):
                continue
            idx = np.nonzero(orig_owner == d)[0]
            cross_l = rt[idx] >= kmax
            same_idx = idx[~cross_l]
            if len(same_idx):
                (via_bound if center_up else unavailable)(
                    same_idx, "server_outage")
            cidx = idx[cross_l]
            if len(cidx):
                # rule 3 from the surviving min: swap (s, t) so the
                # TARGET district's server owns the lane — identical
                # answer by symmetry of min_b B[s,b] + B[t,b]
                sw = prepare_queries(self.data, ts[cidx], ss[cidx])
                surv_dark = np.fromiter(
                    (inj.server_down(int(j)) for j in sw["owner"]),
                    dtype=bool, count=len(cidx))
                ok = cidx[~surv_dark]
                if len(ok):
                    owner[ok] = sw["owner"][~surv_dark]
                    rs[ok] = sw["rs"][~surv_dark]
                    rt[ok] = sw["rt"][~surv_dark]
                    reasons[ok] = "server_outage:rerouted_to_survivor"
                bad = cidx[surv_dark]
                if len(bad):
                    (via_center if center_up else unavailable)(
                        bad, "server_outage")

        # 2. surviving districts join their partials ------------------------
        for d in np.unique(owner[live]):
            d = int(d)
            sel = np.nonzero(live & (owner == d))[0]
            rs_d, rt_d = rs[sel], rt[sel]
            fault_of: dict[int, str] = {}
            stale_of: set[int] = set()
            if (rt_d >= kmax).any() or (rs_d >= kmax).any():
                # districts whose B rows this partial reads (a rerouted
                # lane's rs-side is the ORIGINAL source's district)
                need = np.concatenate([rs_d[rs_d >= kmax],
                                       rt_d[rt_d >= kmax]]) - kmax
                for j in np.unique(np.append(assignment[need], d)):
                    status = self._ensure_rows_faulted(d, int(j))
                    if status == "stale":
                        stale_of.add(int(j))
                    elif status != "ok":
                        fault_of[int(j)] = status
            # per-lane districts (d itself for local row ids)
            src_dist = np.where(
                rs_d >= kmax, assignment[np.maximum(rs_d - kmax, 0)], d)
            tgt_dist = np.where(
                rt_d >= kmax, assignment[np.maximum(rt_d - kmax, 0)], d)
            if fault_of:
                failing = np.array(sorted(fault_of), dtype=np.int64)
                bad = np.isin(src_dist, failing) | np.isin(tgt_dist,
                                                           failing)
                for lane, sd_, td_ in zip(sel[bad], src_dist[bad],
                                          tgt_dist[bad]):
                    f = fault_of.get(int(td_), fault_of.get(int(sd_)))
                    (via_center if center_up else unavailable)(
                        np.array([lane]), f"peer_{f}")
                keep = ~bad
                sel, rs_d, rt_d = sel[keep], rs_d[keep], rt_d[keep]
                src_dist, tgt_dist = src_dist[keep], tgt_dist[keep]
            if stale_of:
                staling = np.array(sorted(stale_of), dtype=np.int64)
                st = np.isin(src_dist, staling) | np.isin(tgt_dist,
                                                          staling)
                codes[sel[st]] = np.uint8(2)
                reasons[sel[st]] = "peer_link_down:stale_border_rows"
            if len(sel):
                vals = lj.join_partial_gathered(
                    self._gather(d, rs_d), self._gather(d, rt_d),
                    use_pallas=self.use_pallas)
                out[sel] = vals
                live[sel] = False
        self.exactness_codes = codes
        self.degraded = reasons
        return out

    # -- accounting ----------------------------------------------------------

    def size_bytes(self) -> int:
        """Host-resident bytes across the coordinator + servers: the
        blocked district tables plus every allocated border-row view
        (both in the storage dtype — 2 bytes/entry quantized)."""
        table = self.data.district_table
        total = int(table.size * table.dtype.itemsize)
        for view in self._bviews:
            if view is not None:
                total += int(view.size * view.dtype.itemsize)
        return total
