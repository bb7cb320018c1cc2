"""jit'd public wrappers for the query-join kernels.

Paper map (anchors refer to PAPER.md / the source paper):

* ``join`` / ``join_gathered`` — Definition 1's 2-hop join λ(s,t,·) over
  dense hub-aligned rows; serves §4.2 rule 3 (cross-district via the
  border table B) and rules 1/2 once districts are densified to the
  combined layout (``edge/engine.py``).
* ``join_sparse`` / ``join_sparse_gathered`` — the same join over padded
  sparse labels L_i; the §4.2 rule-1/2 path during rebuild windows.
* ``join_with_bound`` / ``bound_gathered`` — the fused λ + Local Bound
  (Definition 5) pass that certifies Theorem 3: a rebuild-window answer
  from the *stale* L_i is exact whenever λ ≤ LB, at no extra HBM sweep.
* ``join_sharded_gathered`` — per-device half of the mesh-sharded §4.2
  dispatch: district block sharded over the ``edge`` axis, border table
  replicated at its natural width q (gathered rows are padded to the
  combined width W here, so B never stores W − q dead lanes).
* ``join_sharded_border_gathered`` — the fully-sharded variant: B itself
  is row-sharded, the touched rows are assembled with a ragged
  gather + ``pmin`` collective, then joined exactly like the replicated
  case. No structure in the serving path is replicated anymore.
* ``join_quantized`` / ``join_quantized_gathered`` — the same joins over
  uint16/int16 ``core.quantize`` codes: loads stay narrow in HBM, the
  accumulate widens (int32 on the XLA path, exact float32 on the pallas
  kernel's VMEM tile), the sentinel is the absorbing +inf, and the
  min runs in RAW code units with one final ``· scale`` — so a lossless
  spec serves bit-for-bit the float32 answers at half the bytes. The
  ``quant=`` kwarg threads the same through both sharded entry points;
  in the B-sharded ragged assembly the cross-device ``pmin`` runs on the
  codes widened to int32 (the sentinel doubles as the min identity).
* ``join_partial_gathered`` — the per-edge-server half of the scatter-
  gather read path (``edge/scatter_gather.py``): one server's min-plus
  partial over pre-assembled label rows (its own district block plus
  peer-exchanged border rows). The coordinator consolidates the
  per-server partials with one host-side min — MIN-of-MINs, the
  distance analogue of EdgeLake's remote/local query rewriting.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .kernel import join_lb_pallas, join_pallas
from .ref import join_ref, join_sparse_ref, local_bound_ref

# Batch-size bucket for gathered serving calls: host-side padding up to a
# multiple of PAD_Q keeps the number of distinct jit shapes (and hence
# retraces) bounded no matter how the router buckets a batch.
PAD_Q = 256

# int32 stand-in for +inf in the quantized XLA accumulate: large enough
# that no finite code sum (≤ 2·65534) reaches it, small enough that
# INF_I32 + INF_I32 still fits int32 (1<<30 < 2^31), so a sum of two
# sentinels can never wrap negative and steal the min.
INF_I32 = 1 << 29


def _on_cpu() -> bool:
    return jax.default_backend() == "cpu"


def _ceil_to(n: int, m: int) -> int:
    return max(m, -(-n // m) * m)


def join(s_rows: jnp.ndarray, t_rows: jnp.ndarray, *,
         use_pallas: bool = True) -> jnp.ndarray:
    """Batched dense 2-hop join λ(s,t,B) over gathered label rows."""
    if use_pallas:
        return join_pallas(s_rows, t_rows, interpret=_on_cpu())
    return join_ref(s_rows, t_rows)


def join_with_bound(s_rows: jnp.ndarray, t_rows: jnp.ndarray, *,
                    use_pallas: bool = True
                    ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Fused (λ, LB) — the Theorem-3 serving path during rebuilds."""
    if use_pallas:
        return join_lb_pallas(s_rows, t_rows, interpret=_on_cpu())
    return join_ref(s_rows, t_rows), local_bound_ref(s_rows, t_rows)


def join_quantized(s_codes: jnp.ndarray, t_codes: jnp.ndarray, *,
                   sentinel: int, scale: float,
                   use_pallas: bool = True) -> jnp.ndarray:
    """Dense 2-hop join over quantized label rows (``core.quantize``
    codes), returning float32 distances.

    Both paths reduce in RAW code units and multiply by ``scale`` once
    at the end, so they are bitwise identical to each other — and, for
    a lossless spec (scale = 1 on integral weights), bitwise identical
    to the float32 ``join`` on the dequantized rows:

    * pallas: the kernel takes the codes as stored and widens each VMEM
      tile to exact float32 (sentinel → +inf), so no widened copy of the
      gathered rows reaches HBM; +inf · scale = +inf keeps the sentinel
      an absorbing element;
    * XLA: widen to an int32 accumulate (sentinel → ``INF_I32``), min
      the integer sums, then map ≥ INF_I32 back to +inf.
    """
    if use_pallas:
        raw = join_pallas(s_codes, t_codes, sentinel=sentinel,
                          interpret=_on_cpu())
        return raw * jnp.float32(scale)
    s = jnp.where(s_codes == sentinel, INF_I32,
                  s_codes.astype(jnp.int32))
    t = jnp.where(t_codes == sentinel, INF_I32,
                  t_codes.astype(jnp.int32))
    m = jnp.min(s + t, axis=1)
    return jnp.where(m >= INF_I32, jnp.inf,
                     m.astype(jnp.float32) * jnp.float32(scale))


def join_sparse(hs, ds, ht, dt) -> jnp.ndarray:
    """Padded sparse-label join (local indexes); pure-XLA — the O(L²)
    mask fits VREGs for the small local label widths."""
    return join_sparse_ref(hs, ds, ht, dt)


# -- gathered serving entry points (host arrays in, host arrays out) --------

def join_gathered(table: np.ndarray, ss: np.ndarray, ts: np.ndarray, *,
                  use_pallas: bool = True) -> np.ndarray:
    """Rule-3 serving join: gather dense border-label rows ``table[ss]`` /
    ``table[ts]`` and reduce on device. The batch is inf-padded to a
    multiple of PAD_Q (padding rows join to +inf and are sliced off)."""
    qn = len(ss)
    if qn == 0 or table.shape[1] == 0:
        return np.full(qn, np.inf, dtype=np.float32)
    qp = _ceil_to(qn, PAD_Q)
    s_rows = np.full((qp, table.shape[1]), np.inf, dtype=np.float32)
    t_rows = np.full((qp, table.shape[1]), np.inf, dtype=np.float32)
    s_rows[:qn] = table[ss]
    t_rows[:qn] = table[ts]
    out = join(jnp.asarray(s_rows), jnp.asarray(t_rows),
               use_pallas=use_pallas)
    return np.asarray(out)[:qn]


def join_quantized_gathered(table: np.ndarray, ss: np.ndarray,
                            ts: np.ndarray, *, sentinel: int,
                            scale: float,
                            use_pallas: bool = True) -> np.ndarray:
    """Quantized twin of ``join_gathered``: the table holds integer
    codes and the batch is padded with the sentinel (the quantized
    +inf, which never wins the min) instead of float +inf."""
    qn = len(ss)
    if qn == 0 or table.shape[1] == 0:
        return np.full(qn, np.inf, dtype=np.float32)
    qp = _ceil_to(qn, PAD_Q)
    s_rows = np.full((qp, table.shape[1]), sentinel, dtype=table.dtype)
    t_rows = np.full((qp, table.shape[1]), sentinel, dtype=table.dtype)
    s_rows[:qn] = table[ss]
    t_rows[:qn] = table[ts]
    out = join_quantized(jnp.asarray(s_rows), jnp.asarray(t_rows),
                         sentinel=sentinel, scale=scale,
                         use_pallas=use_pallas)
    return np.asarray(out)[:qn]


def join_partial_gathered(s_rows: np.ndarray, t_rows: np.ndarray, *,
                          use_pallas: bool = True) -> np.ndarray:
    """One edge server's scatter-gather partial: a dense 2-hop join over
    label rows the caller already assembled (district block rows for the
    server's local lanes, own/peer border rows for its cross lanes).
    Same kernel, same PAD_Q batch bucketing, and the same inf-padding
    convention as the engine joins — a lane's answer depends only on its
    own two rows, so the partial is bit-for-bit the lane's value in the
    sharded engine's pre-``pmin`` per-device vector."""
    qn = len(s_rows)
    if qn == 0 or s_rows.shape[1] == 0:
        return np.full(qn, np.inf, dtype=np.float32)
    qp = _ceil_to(qn, PAD_Q)
    sp = np.full((qp, s_rows.shape[1]), np.inf, dtype=np.float32)
    tp = np.full((qp, t_rows.shape[1]), np.inf, dtype=np.float32)
    sp[:qn], tp[:qn] = s_rows, t_rows
    out = join(jnp.asarray(sp), jnp.asarray(tp), use_pallas=use_pallas)
    return np.asarray(out)[:qn]


def join_sparse_gathered(hubs: np.ndarray, dists: np.ndarray,
                         ss: np.ndarray, ts: np.ndarray) -> np.ndarray:
    """Rule-1/2 serving join over a district's padded sparse labels
    (local-id queries). Padding rows carry hub -1 → join to +inf."""
    qn = len(ss)
    if qn == 0:
        return np.zeros(0, dtype=np.float32)
    qp = _ceil_to(qn, PAD_Q)
    width = hubs.shape[1]
    hs = -np.ones((qp, width), dtype=np.int32)
    ht = -np.ones((qp, width), dtype=np.int32)
    ds = np.full((qp, width), np.inf, dtype=np.float32)
    dt = np.full((qp, width), np.inf, dtype=np.float32)
    hs[:qn], ds[:qn] = hubs[ss], dists[ss]
    ht[:qn], dt[:qn] = hubs[ts], dists[ts]
    out = join_sparse(jnp.asarray(hs), jnp.asarray(ds),
                      jnp.asarray(ht), jnp.asarray(dt))
    return np.asarray(out)[:qn].astype(np.float32)


def join_sharded_gathered(block: jnp.ndarray, btable: jnp.ndarray,
                          owner: jnp.ndarray, rs: jnp.ndarray,
                          rt: jnp.ndarray, *, axis: str,
                          use_pallas: bool = True,
                          quant: tuple[int, float] | None = None
                          ) -> jnp.ndarray:
    """Per-device half of the mesh-sharded serving join; runs INSIDE a
    ``shard_map`` over ``axis``. ``block`` is this device's slice of the
    district tables (width W), ``btable`` the replicated border table at
    its *natural* width q ≤ W (storing B at W would waste n·(W−q) dead
    entries of resident bytes per device; instead the gathered
    (batch, q) rows are padded to W here with the +inf element, which is
    bit-for-bit equivalent because +inf lanes never win a min-plus
    join). Row ids ``rs``/``rt`` below ``block.shape[0]`` gather from
    the block, the rest from B (offset past the block); the dense join
    runs on every device, lanes whose ``owner`` isn't this device are
    masked to +inf, and a ``pmin`` over the axis assembles the answer
    vector.

    With ``quant=(sentinel, scale)`` the tables hold ``core.quantize``
    codes: padding uses the sentinel and the join runs through
    ``join_quantized`` (the answer vector is float32 either way)."""
    dev = jax.lax.axis_index(axis)
    cross_base = block.shape[0]
    wpad = block.shape[1] - btable.shape[1]
    assert wpad >= 0, "border table wider than the combined width"
    pad_val = jnp.inf if quant is None else block.dtype.type(quant[0])

    def gather(rows):
        # two gathers + a select keeps both tables device-resident (no
        # per-dispatch [block; B] concat, which would cost table-sized
        # memory traffic per call)
        local = rows < cross_base
        dist = block[jnp.where(local, rows, 0)]
        bord = btable[jnp.where(local, 0, rows - cross_base)]
        if wpad:
            bord = jnp.pad(bord, ((0, 0), (0, wpad)),
                           constant_values=pad_val)
        return jnp.where(local[:, None], dist, bord)

    if quant is None:
        ans = join(gather(rs), gather(rt), use_pallas=use_pallas)
    else:
        ans = join_quantized(gather(rs), gather(rt), sentinel=quant[0],
                             scale=quant[1], use_pallas=use_pallas)
    return _answer_pmin(owner == dev, ans, axis)


def _answer_pmin(owned: jnp.ndarray, ans: jnp.ndarray,
                 axis: str) -> jnp.ndarray:
    """The answer vector over ``axis``: each device keeps the lanes it
    owns and contributes +inf for the rest."""
    with jax.named_scope("answer_pmin"):
        return jax.lax.pmin(jnp.where(owned, ans, jnp.inf), axis)


def join_sharded_border_gathered(block: jnp.ndarray, bshard: jnp.ndarray,
                                 owner: jnp.ndarray, rs: jnp.ndarray,
                                 rt: jnp.ndarray, *, axis: str,
                                 use_pallas: bool = True,
                                 quant: tuple[int, float] | None = None
                                 ) -> jnp.ndarray:
    """Fully-sharded serving join: like ``join_sharded_gathered`` but the
    border table is ROW-SHARDED over ``axis`` too — ``bshard`` is this
    device's ``ceil(n/E)`` row-slice of B at natural width q. Runs INSIDE
    a ``shard_map``.

    Row ids keep the replicated convention (>= ``block.shape[0]`` means
    "row v of B"), so the host routing pass is layout-agnostic. The
    touched B rows are assembled by a ragged gather + ``pmin``: each
    device gathers the rows it owns (others contribute +inf), and ONE
    fused (2·batch, q) min-collective covering both endpoints leaves
    every device holding exactly the B rows this batch needs —
    collective traffic scales with the batch, never with n, and a
    single launch amortizes the collective latency. The assembled rows
    are padded to the combined width W with the +inf element and joined
    exactly like the replicated case.

    With ``quant=(sentinel, scale)`` the tables hold ``core.quantize``
    codes and the ragged assembly ``pmin`` runs on the codes widened to
    int32 — the sentinel (the dtype maximum) is the min identity, so
    non-owners contribute it instead of +inf. The TPU compiler packs a
    16-bit min all-reduce two codes to a 32-bit word, and on a v5e 2x2
    mesh that collective returned wrong B rows; the widened collective
    moves as many bytes as the float32 layout's."""
    dev = jax.lax.axis_index(axis)
    cross_base = block.shape[0]
    rows_pd = bshard.shape[0]       # = ceil(n/E) ≥ 1 whenever n ≥ 1
    wpad = block.shape[1] - bshard.shape[1]
    assert wpad >= 0, "border shard wider than the combined width"
    pad_val = jnp.inf if quant is None else block.dtype.type(quant[0])

    def ragged(rows):
        local = rows < cross_base
        gid = jnp.where(local, 0, rows - cross_base)
        own = (~local) & (gid // rows_pd == dev)
        vals = bshard[jnp.where(own, gid % rows_pd, 0)]
        return jnp.where(own[:, None], vals, pad_val)

    # after the pmin every device holds the true B row for each cross
    # lane (non-owners contributed the min identity); s and t lanes are
    # stacked so both endpoints ride one collective launch
    with jax.named_scope("border_rows"):
        both = jnp.concatenate([ragged(rs), ragged(rt)])
        if quant is None:
            both = jax.lax.pmin(both, axis)
        else:
            both = jax.lax.pmin(both.astype(jnp.int32),
                                axis).astype(both.dtype)
    if wpad:
        both = jnp.pad(both, ((0, 0), (0, wpad)),
                       constant_values=pad_val)
    bs_rows, bt_rows = jnp.split(both, 2)

    def gather(rows, bord):
        local = rows < cross_base
        dist = block[jnp.where(local, rows, 0)]
        return jnp.where(local[:, None], dist, bord)

    if quant is None:
        ans = join(gather(rs, bs_rows), gather(rt, bt_rows),
                   use_pallas=use_pallas)
    else:
        ans = join_quantized(gather(rs, bs_rows), gather(rt, bt_rows),
                             sentinel=quant[0], scale=quant[1],
                             use_pallas=use_pallas)
    return _answer_pmin(owner == dev, ans, axis)


def bound_gathered(border_dist: np.ndarray, ss: np.ndarray,
                   ts: np.ndarray, *, use_pallas: bool = True) -> np.ndarray:
    """Theorem-3 serving certificate: LB[i] = min_b bd[ss[i]] + min_b'
    bd[ts[i]] via the fused join_with_bound pass over gathered
    vertex→border distance rows (the λ output of the fused kernel is the
    via-one-border upper bound and is discarded here)."""
    qn = len(ss)
    if qn == 0 or border_dist.shape[1] == 0:
        return np.full(qn, np.inf, dtype=np.float32)
    qp = _ceil_to(qn, PAD_Q)
    s_rows = np.full((qp, border_dist.shape[1]), np.inf, dtype=np.float32)
    t_rows = np.full((qp, border_dist.shape[1]), np.inf, dtype=np.float32)
    s_rows[:qn] = border_dist[ss]
    t_rows[:qn] = border_dist[ts]
    _, lb = join_with_bound(jnp.asarray(s_rows), jnp.asarray(t_rows),
                            use_pallas=use_pallas)
    return np.asarray(lb)[:qn]
