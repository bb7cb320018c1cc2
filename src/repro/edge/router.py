"""System facade: center + all edge servers + engine snapshots,
version-aware.

``EdgeSystem`` is the functional model of the deployment (the discrete-
event simulator adds time on top; the sharded_oracle maps the same logic
onto a device mesh).  The request plane — §4.2 routing, typed results,
rebuild-window policy — lives in ``repro.serve.service``; get a front
door with ``EdgeSystem.service()``.  (The historical entry points
``query`` / ``query_batched`` / ``query_many`` were deprecated shims
for two PRs and are now removed.)

Paper map: the service planes implement the §4.2 query rules (rule 1
same-district local, rule 2 same-district via another client's server,
rule 3 cross-district through the border table B); during a rebuild
window (center pushed a new index version, shortcuts not yet installed)
answers are served from the stale L_i under the Theorem-3
rebuild-window certificate (λ ≤ Local Bound ⇒ still exact), and the
uncertified residue is resolved per the policy's rebuild mode.
``choose_layout`` is the one place the steady-state engine's layout is
decided; ``_current_engine`` snapshots one index version into an engine
of that layout and swaps it — including the device-resident B shards —
whenever the center's version, the placement or the layout moves (see
docs/ARCHITECTURE.md).
"""
from __future__ import annotations

import multiprocessing
import os
from concurrent.futures import ProcessPoolExecutor
from dataclasses import dataclass, field
from typing import TYPE_CHECKING

import numpy as np

from .. import obs
from ..core.graph import Graph
from ..core.partition import Partition
from .center import ComputingCenter
from .server import EdgeServer, build_server

if TYPE_CHECKING:                                   # pragma: no cover
    from ..serve.service import DistanceService, ServingPolicy

# auto-pick threshold for row-sharding the border table B: replicating B
# costs n·q·4 bytes per device and zero collectives, so it stays
# replicated until it is big enough to matter
SHARD_BORDER_AUTO_BYTES = 64 << 20

# auto-pick threshold for quantized label storage: once the float32
# index footprint (B + dense district tables) crosses this, the engines
# store uint16 codes instead — but ONLY when the fitted spec is lossless
# (integer-second weights), so auto never changes a single answer
QUANT_AUTO_BYTES = 32 << 20

# auto-pick for ``EdgeSystem.deploy``: below this many vertices the
# district builds take less time than starting worker processes does
DEPLOY_POOL_MIN_VERTICES = 8192


@dataclass(frozen=True)
class EngineLayout:
    """How the steady-state engine holds one index version."""
    sharded: bool           # district tables sharded over the devices
    shard_border: bool      # B row-sharded (only inside the sharded engine)
    label_dtype: str        # storage: "float32", "uint16" or "int16"


def choose_layout(engine: str, shard_border: bool | None, label_dtype: str,
                  num_devices: int, border_bytes: int, index_bytes: int,
                  lossless: bool) -> EngineLayout:
    """The steady-state engine layout, decided in one place.

    ``engine`` (``auto``/``replicated``/``sharded``), ``shard_border``
    and ``label_dtype`` are the ``ServingPolicy`` knobs; the rest is
    what the system observes: its device count, B's float32 bytes
    (n·q·4), the float32 bytes of the whole index (B plus the dense
    district tables), and whether the uint16 spec fitted to the tables
    is lossless.

    * sharded: under ``auto`` iff there is more than one device;
    * B row-sharded: only inside the sharded engine, and under ``None``
      once B is over SHARD_BORDER_AUTO_BYTES;
    * storage: under ``auto`` uint16 once the index is over
      QUANT_AUTO_BYTES and the fit is lossless (so auto never changes
      an answer), else float32; an explicit dtype is honored even when
      the fit is lossy.
    """
    sharded = num_devices > 1 if engine == "auto" else engine == "sharded"
    border = sharded and (border_bytes > SHARD_BORDER_AUTO_BYTES
                          if shard_border is None else shard_border)
    if label_dtype == "auto":
        label_dtype = ("uint16" if index_bytes > QUANT_AUTO_BYTES and lossless
                       else "float32")
    return EngineLayout(sharded, border, label_dtype)


def usable_cpus() -> int:
    """CPUs this process may run on, capped by a cgroup v2 CPU quota
    (a container's share of a larger host) where one is set."""
    cpus = (len(os.sched_getaffinity(0)) if hasattr(os, "sched_getaffinity")
            else os.cpu_count() or 1)
    try:
        with open("/sys/fs/cgroup/cpu.max") as f:
            quota, period = f.read().split()[:2]
        if quota != "max":
            cpus = min(cpus, max(1, int(quota) // int(period)))
    except (OSError, ValueError):
        pass
    return cpus


def deploy_workers(g: Graph, part: Partition) -> int:
    """Processes ``EdgeSystem.deploy`` builds the edge servers in: one
    per usable CPU, at most one per district, and 1 (in this process)
    for a network under DEPLOY_POOL_MIN_VERTICES."""
    if g.num_vertices < DEPLOY_POOL_MIN_VERTICES:
        return 1
    return max(1, min(usable_cpus(), part.num_districts))


@dataclass
class EdgeSystem:
    graph: Graph
    partition: Partition
    center: ComputingCenter
    servers: list[EdgeServer]
    stats: dict = field(default_factory=lambda: {
        "rule1": 0, "rule2": 0, "rule3": 0, "lb_certified": 0,
        "lb_fallback_attempts": 0})
    # district → edge-host routing table (repro.topo.rebalance); None =
    # the blocked default layout.  ``migrate`` swaps it atomically — its
    # version joins the engine cache key, so the next batch routes on
    # the new table while in-flight batches keep the snapshot (= the old
    # owner) they started with
    placement: object | None = None
    # steady-state serving engine: one index version in one layout,
    # keyed on (snapshot_key(), layout); setting _engine to None forces
    # the next call to rebuild
    _engine: object | None = field(default=None, repr=False)
    _engine_key: tuple | None = field(default=None, repr=False)
    # (index version, uint16 spec fitted to that version's tables)
    _fit: tuple | None = field(default=None, repr=False)

    @classmethod
    def deploy(cls, g: Graph, part: Partition,
               builder: str = "reference") -> "EdgeSystem":
        """Center build, then every district's edge server (L_i, then L_i⁺
        from the center's shortcuts).  The servers are independent, as the
        paper's edge hosts are, so a large network builds them in
        ``deploy_workers`` processes.  Either way each server is the
        same, bit for bit."""
        center = ComputingCenter(g, part, builder=builder)
        with obs.span("repro.deploy.center"):
            center.rebuild()
        m = part.num_districts
        workers = deploy_workers(g, part)
        if workers <= 1:
            servers = []
            for i in range(m):
                with obs.span("repro.deploy.server", district=i):
                    servers.append(build_server(
                        g, part, i, center.shortcuts_for(i), center.version))
            return cls(g, part, center, servers)
        shortcuts = [center.shortcuts_for(i) for i in range(m)]
        # spawned, not forked: the parent may hold an accelerator runtime.
        # Each job carries the network (under a MB): a worker's start then
        # never waits on a large pickle
        ctx = multiprocessing.get_context("spawn")
        with obs.span("repro.deploy.server", districts=m, workers=workers), \
                ProcessPoolExecutor(workers, mp_context=ctx) as pool:
            servers = list(pool.map(build_server, [g] * m, [part] * m,
                                    range(m), shortcuts,
                                    [center.version] * m))
        return cls(g, part, center, servers)

    def apply_traffic_update(self, new_weights: np.ndarray,
                             incremental: bool = False) -> dict:
        """Traffic-epoch update cycle; returns timings.

        ``incremental=False`` — the paper's full cycle: every edge server
        refreshes its local index, the center rebuilds B from scratch,
        shortcuts are pushed back down everywhere.

        ``incremental=True`` — delta-scoped cycle (``repro.update``):
        only districts with a dirty intra edge refresh their local index,
        the center repairs B (bit-for-bit equal to a full rebuild), and
        shortcuts are reinstalled only where the shortcut matrix or the
        local index actually moved.  Clean districts' servers just adopt
        the new version number: their L_i⁺ inputs are bitwise unchanged,
        so they keep serving without ever entering a rebuild window, and
        the engine swap re-densifies only the touched districts (clean
        ``LocalIndex`` objects keep their cached dense tables).
        """
        if not incremental:
            g2 = self.graph.with_weights(new_weights)
            self.graph = g2
            local_s = [srv.refresh_local(g2, self.partition)
                       for srv in self.servers]
            bl_s = self.center.rebuild(new_weights)
            shortcut_s = [srv.install_shortcuts(
                g2, self.partition,
                self.center.shortcuts_for(srv.district_id),
                self.center.version) for srv in self.servers]
            return {"local_refresh_s": local_s, "bl_rebuild_s": bl_s,
                    "shortcut_install_s": shortcut_s,
                    "incremental": False}
        rep = self.center.apply_delta(new_weights)
        if rep["noop"]:
            return {"local_refresh_s": {}, "bl_rebuild_s": 0.0,
                    "shortcut_install_s": {}, "incremental": True,
                    "dirty_districts": [], "stale_shortcut_districts": [],
                    "clean_districts": list(range(len(self.servers)))}
        g2 = self.center.graph          # same topology, new weights
        self.graph = g2
        delta = rep["delta"]
        dirty = set(int(i) for i in delta.dirty_districts)
        stale = set(rep["stale_districts"])
        local_s: dict[int, float] = {}
        shortcut_s: dict[int, float] = {}
        clean: list[int] = []
        for i, srv in enumerate(self.servers):
            if i in dirty:
                local_s[i] = srv.refresh_local(g2, self.partition)
            if i in dirty or i in stale or srv.augmented is None:
                shortcut_s[i] = srv.install_shortcuts(
                    g2, self.partition, self.center.shortcuts_for(i),
                    self.center.version)
            else:
                # nothing this server depends on moved — keep serving
                srv.augmented_version = self.center.version
                clean.append(i)
        return {"local_refresh_s": local_s,
                "bl_rebuild_s": rep["seconds"],
                "shortcut_install_s": shortcut_s,
                "incremental": rep["incremental"],
                "dirty_districts": sorted(dirty),
                "stale_shortcut_districts": sorted(stale),
                "clean_districts": clean}

    def apply_topology_update(self, g_new: Graph,
                              incremental: bool = True) -> dict:
        """Structural update cycle — road closures/openings.

        ``incremental=True`` (default): classify the topology diff
        (``repro.topo``), repair B with the scoped structural path, and
        refresh only the edge servers whose inputs moved — a district's
        local index reads its intra arc set (dirty districts refresh)
        and its Definition-4 border list (every server refreshes when
        ``border_changed``).  ``incremental=False`` runs the paper's
        full redeploy cycle.  Either way the partition and vertex set
        are fixed; repartitioning is a separate concern (``migrate``).
        """
        if not incremental:
            self.graph = g_new
            self.center.graph = g_new
            self.center._border_lists = None       # topology moved
            local_s = [srv.refresh_local(g_new, self.partition)
                       for srv in self.servers]
            bl_s = self.center.rebuild()
            shortcut_s = [srv.install_shortcuts(
                g_new, self.partition,
                self.center.shortcuts_for(srv.district_id),
                self.center.version) for srv in self.servers]
            return {"local_refresh_s": local_s, "bl_rebuild_s": bl_s,
                    "shortcut_install_s": shortcut_s,
                    "incremental": False, "border_changed": True}
        rep = self.center.apply_structural(g_new)
        self.graph = self.center.graph
        if rep["noop"]:
            return {"local_refresh_s": {}, "bl_rebuild_s": 0.0,
                    "shortcut_install_s": {}, "incremental": True,
                    "border_changed": False,
                    "dirty_districts": [], "stale_shortcut_districts": [],
                    "clean_districts": list(range(len(self.servers)))}
        delta = rep["delta"]
        if rep["border_changed"]:
            # border sets moved: every server's L_i border rows are laid
            # out against the new border lists — refresh everywhere
            dirty = set(range(len(self.servers)))
        else:
            dirty = set(int(i) for i in delta.dirty_districts)
        stale = set(rep["stale_districts"])
        local_s: dict[int, float] = {}
        shortcut_s: dict[int, float] = {}
        clean: list[int] = []
        for i, srv in enumerate(self.servers):
            if i in dirty:
                local_s[i] = srv.refresh_local(g_new, self.partition)
            if i in dirty or i in stale or srv.augmented is None:
                shortcut_s[i] = srv.install_shortcuts(
                    g_new, self.partition, self.center.shortcuts_for(i),
                    self.center.version)
            else:
                srv.augmented_version = self.center.version
                clean.append(i)
        return {"local_refresh_s": local_s,
                "bl_rebuild_s": rep["seconds"],
                "shortcut_install_s": shortcut_s,
                "incremental": rep["incremental"],
                "border_changed": rep["border_changed"],
                "dirty_districts": sorted(dirty),
                "stale_shortcut_districts": sorted(stale),
                "clean_districts": clean}

    def migrate(self, plan_or_placement) -> dict:
        """Install a new district → host placement atomically (the
        ``RebalancePlanner`` execute step).

        The placement version joins the engine cache key, so
        the swap is a pointer write: batches planned after this call
        route on the new table (the next ``_current_engine`` call
        re-packs the moved districts' blocks — unmoved districts'
        cached dense tables are memcpy'd, not recomputed); batches
        already in flight keep the engine snapshot — and therefore the
        old owner — they started with.  Index versions are untouched,
        so exactness is preserved through the swap."""
        plan = plan_or_placement
        placement = getattr(plan, "placement", plan)
        m = self.partition.num_districts
        if placement.num_districts != m:
            raise ValueError(f"placement covers {placement.num_districts} "
                             f"districts, system has {m}")
        old = self.placement
        self.placement = placement
        return {"placement_version": placement.version,
                "num_hosts": placement.num_hosts,
                "moved_districts":
                    [] if old is None and plan is placement
                    else [mv.district for mv in getattr(plan, "moves", ())],
                "previous_version":
                    None if old is None else old.version}

    def service(self, policy: "ServingPolicy | None" = None
                ) -> "DistanceService":
        """A typed request-plane front door over this system (see
        ``repro.serve.service``).  Each call returns a fresh service
        with its own counters; the engine snapshot underneath is shared
        through ``_current_engine``'s cache, so services are cheap."""
        from ..serve.service import DistanceService
        return DistanceService(self, policy)

    def _merge_stats(self, counters: dict) -> None:
        for k, v in counters.items():
            self.stats[k] += v

    def snapshot_key(self) -> tuple:
        """What an engine snapshot is valid for besides its layout: the
        center's index version and the placement's key.  Outside a
        rebuild window every server is at the center's version."""
        placement = self.placement
        return (self.center.version,
                None if placement is None else placement.key())

    def _fitted_spec(self):
        """The uint16 ``QuantSpec`` fitted to the current index's
        tables, memoized per index version (the fit reads every
        table)."""
        from ..core.quantize import fit_label_spec
        version = self.center.version
        if self._fit is None or self._fit[0] != version:
            self._fit = (version, fit_label_spec(
                self.center.border_labels.table,
                [srv.augmented for srv in self.servers]))
        return self._fit[1]

    def layout(self, policy: "ServingPolicy | None" = None) -> EngineLayout:
        """``choose_layout`` for ``policy`` (None: the default policy) on
        this backend's devices and the current index's tables.  Call it
        only outside a rebuild window."""
        import jax
        engine, shard_border, label_dtype = (
            ("auto", None, "auto") if policy is None
            else (policy.engine, policy.shard_border, policy.label_dtype))
        btable = self.center.border_labels.table
        index_bytes = 4 * (btable.size + sum(len(srv.augmented.vertices) ** 2
                                             for srv in self.servers))
        return choose_layout(engine, shard_border, label_dtype,
                             len(jax.devices()), 4 * btable.size,
                             index_bytes, self._fitted_spec().lossless)

    def _resolve_quant(self, label_dtype: str):
        """The ``QuantSpec`` the engines pack with for a storage dtype
        ``choose_layout`` resolved (None for float32 storage)."""
        if label_dtype == "float32":
            return None
        if label_dtype == "uint16":
            return self._fitted_spec()
        from ..core.quantize import LABEL_DTYPES, fit_label_spec
        return fit_label_spec(self.center.border_labels.table,
                              [srv.augmented for srv in self.servers],
                              dtype=LABEL_DTYPES[label_dtype])

    def _current_engine(self, policy: "ServingPolicy | None" = None):
        """Engine snapshot for the current index version in the layout
        ``choose_layout`` picks for ``policy``, or None while any
        district's shortcuts are stale (rebuild window).  The replicated
        layout is ``BatchedQueryEngine``; the sharded one is
        ``ShardedBatchedEngine``, whose district tables are sharded over
        the ``edge`` mesh axis so the table scales past one device's
        memory, with B itself row-sharded where the layout says so.
        One engine is cached, keyed on ``snapshot_key()`` and the
        layout."""
        if any(srv.augmented is None
               or srv.augmented_version != self.center.version
               for srv in self.servers):
            return None
        layout = self.layout(policy)
        key = (self.snapshot_key(), layout)
        if self._engine is None or self._engine_key != key:
            import jax

            from .engine import BatchedQueryEngine, ShardedBatchedEngine
            quant = self._resolve_quant(layout.label_dtype)
            # drop the stale engine's device buffers BEFORE building the
            # replacement: holding both doubles peak device memory at
            # every rebuild, exactly where sharded tables run near limits
            # (for the sharded engines this swap also replaces the
            # device-resident B shards with the new version's)
            self._engine = None
            btable = self.center.border_labels.table
            locals_ = [srv.augmented for srv in self.servers]
            if layout.sharded:
                # the placement maps districts to edge hosts; it becomes
                # the device layout when the host and device counts line
                # up (the simulator's one-host-per-device model)
                placement = self.placement
                host_of = placement.host_of \
                    if placement is not None \
                    and placement.num_hosts == len(jax.devices()) else None
                self._engine = ShardedBatchedEngine(
                    btable, locals_, self.partition.assignment,
                    shard_border=layout.shard_border, quant=quant,
                    placement=host_of)
            else:
                self._engine = BatchedQueryEngine(
                    btable, locals_, self.partition.assignment, quant=quant)
            self._engine_key = key
        return self._engine

    def current_engine(self, policy: "ServingPolicy | None" = None):
        """Public accessor for the active serving-engine snapshot in
        ``policy``'s layout (None during a rebuild window). Use this —
        not the underscore internals — to inspect which layout the
        auto-pick chose and its ``size_bytes()`` footprint."""
        return self._current_engine(policy)

    def query_loop(self, ss: np.ndarray, ts: np.ndarray) -> np.ndarray:
        """Per-query Python reference path (parity + benchmark baseline);
        the ``ScalarLoopPlane`` of the request plane."""
        svc = self.service()
        out = svc.scalar_plane().execute(np.asarray(ss, dtype=np.int64),
                                         np.asarray(ts, dtype=np.int64))
        self._merge_stats(svc.stats)
        return out
