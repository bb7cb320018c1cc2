"""Batched 2-hop query join as a Pallas TPU kernel.

The serving hot loop: for a batch of Q queries the gathered source/target
border-label rows (Q, W) are streamed through VMEM in (bq, ·) tiles and
reduced to a per-query min — one VPU add+min per element, purely
memory-bound, so the kernel's job is simply to keep the tiles streaming
(hub axis innermost, output tile revisited in-register). The rows arrive
in the dtype they are stored in: float32, or ``core.quantize`` integer
codes that the kernel widens on the VMEM tile, so no widened or padded
copy of the gathered rows is ever written to HBM.

A fused variant also emits the Local Bound (Definition 5) in the same pass
— certifying Theorem 3 costs no extra HBM traffic during rebuild windows.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


# VMEM the double-buffered tiles of both operands may take when a block
# spans the whole hub width; wider rows are tiled by ``bh`` instead.
WHOLE_WIDTH_VMEM_BYTES = 4 << 20


def _widen(x: jnp.ndarray, sentinel: int | None) -> jnp.ndarray:
    """A tile as float32. Integer codes widen code -> int32 -> float32
    with the sentinel -> +inf (Mosaic lowers no direct 16-bit integer to
    float cast); exact, since codes < 2^16 << 2^24, so every value and
    every pairwise sum is representable."""
    if sentinel is None:
        return x.astype(jnp.float32)
    c = x.astype(jnp.int32)
    return jnp.where(c == sentinel, jnp.inf, c.astype(jnp.float32))


def _join_kernel(s_ref, t_ref, o_ref, *, sentinel, width, bh):
    h = pl.program_id(1)

    @pl.when(h == 0)
    def _init():
        o_ref[...] = jnp.full_like(o_ref[...], jnp.inf)
    tile = _widen(s_ref[...], sentinel) + _widen(t_ref[...], sentinel)
    if width % bh:
        # the last hub block runs past the row: its tail lanes are undefined
        lane = h * bh + jax.lax.broadcasted_iota(jnp.int32, tile.shape, 1)
        tile = jnp.where(lane < width, tile, jnp.inf)
    # lane-dense output: a row block's answers lie along one (1, bq) row
    o_ref[...] = jnp.minimum(o_ref[...],
                             jnp.min(tile, axis=1).reshape(o_ref.shape))


def _join_lb_kernel(s_ref, t_ref, o_ref, lb_ref):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        o_ref[...] = jnp.full_like(o_ref[...], jnp.inf)
        lb_ref[...] = jnp.full_like(lb_ref[...], jnp.inf)
    s = s_ref[...]
    t = t_ref[...]
    o_ref[...] = jnp.minimum(o_ref[...],
                             jnp.min(s + t, axis=1, keepdims=True))
    # LB needs min_b s and min_b' t separately; pack both into lb_ref lanes
    smin = jnp.min(s, axis=1, keepdims=True)
    tmin = jnp.min(t, axis=1, keepdims=True)
    lb_ref[...] = jnp.minimum(lb_ref[...],
                              jnp.concatenate([smin, tmin], axis=1))


def _pad_rows(x: jnp.ndarray, bq: int, bh: int,
              value=jnp.inf) -> jnp.ndarray:
    p0 = (-x.shape[0]) % bq
    p1 = (-x.shape[1]) % bh
    if p0 or p1:
        x = jnp.pad(x, ((0, p0), (0, p1)), constant_values=value)
    return x


@functools.partial(jax.jit,
                   static_argnames=("sentinel", "bq", "bh", "interpret"))
def join_pallas(s_rows: jnp.ndarray, t_rows: jnp.ndarray, *,
                sentinel: int | None = None, bq: int = 256, bh: int = 512,
                interpret: bool = False) -> jnp.ndarray:
    """out[i] = min_j s_rows[i,j] + t_rows[i,j].

    ``s_rows``/``t_rows`` are float rows, or integer label codes when
    ``sentinel`` (the code of +inf) is given; codes are joined in raw
    code units and the answer is float32. A row block spans the whole
    hub width W when its double-buffered tiles fit
    ``WHOLE_WIDTH_VMEM_BYTES``; otherwise the hub axis is tiled by ``bh``
    (a multiple of 128 on the chip) and the lanes past W are masked in
    the kernel. Only the row axis is padded, and only where the batch is
    not a multiple of ``bq`` (the serving batch is a PAD_Q multiple)."""
    qn, hub = s_rows.shape
    assert t_rows.shape == (qn, hub) and t_rows.dtype == s_rows.dtype
    if 4 * bq * hub * s_rows.dtype.itemsize <= WHOLE_WIDTH_VMEM_BYTES:
        bh = hub
    pad = jnp.inf if sentinel is None else sentinel
    s_rows = _pad_rows(s_rows, bq, 1, pad)
    t_rows = _pad_rows(t_rows, bq, 1, pad)
    qp = s_rows.shape[0]
    out = pl.pallas_call(
        functools.partial(_join_kernel, sentinel=sentinel, width=hub,
                          bh=bh),
        grid=(qp // bq, pl.cdiv(hub, bh)),
        in_specs=[
            pl.BlockSpec((bq, bh), lambda i, h: (i, h)),
            pl.BlockSpec((bq, bh), lambda i, h: (i, h)),
        ],
        out_specs=pl.BlockSpec((None, 1, bq), lambda i, h: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((qp // bq, 1, bq), jnp.float32),
        interpret=interpret,
    )(s_rows, t_rows)
    out = out.reshape(qp)[:qn]
    return out if sentinel is not None else out.astype(s_rows.dtype)


@functools.partial(jax.jit, static_argnames=("bq", "bh", "interpret"))
def join_lb_pallas(s_rows: jnp.ndarray, t_rows: jnp.ndarray, *,
                   bq: int = 256, bh: int = 512, interpret: bool = False
                   ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Fused (λ, LB) pass: returns (join, local_bound) per query row."""
    qn, hub = s_rows.shape
    s32 = _pad_rows(s_rows.astype(jnp.float32), bq, bh)
    t32 = _pad_rows(t_rows.astype(jnp.float32), bq, bh)
    qp, hp = s32.shape
    lam, lb2 = pl.pallas_call(
        _join_lb_kernel,
        grid=(qp // bq, hp // bh),
        in_specs=[
            pl.BlockSpec((bq, bh), lambda i, h: (i, h)),
            pl.BlockSpec((bq, bh), lambda i, h: (i, h)),
        ],
        out_specs=[
            pl.BlockSpec((bq, 1), lambda i, h: (i, 0)),
            pl.BlockSpec((bq, 2), lambda i, h: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((qp, 1), jnp.float32),
            jax.ShapeDtypeStruct((qp, 2), jnp.float32),
        ],
        interpret=interpret,
    )(s32, t32)
    lam = lam[:qn, 0]
    lb = lb2[:qn, 0] + lb2[:qn, 1]
    return lam.astype(s_rows.dtype), lb.astype(s_rows.dtype)
