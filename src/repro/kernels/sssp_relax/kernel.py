"""Blocked Floyd–Warshall APSP as Pallas TPU kernels.

The per-district APSP (stage A of the hierarchical Border-Labeling builder
and the whole local-index distance computation) is the classic three-phase
blocked FW: for each pivot block kb along the diagonal,

  phase 1  close the (bk,bk) pivot block in-register (bk in-block pivots);
  phase 2  relax the pivot block-row and block-column against the closed
           pivot (one min-plus product each);
  phase 3  relax every remaining (i,j) tile against the updated column
           tile (i,kb) and row tile (kb,j).

All three phases are VPU min-plus tiles with the same VMEM blocking as
`kernels/minplus`; phases run as separate pallas_calls per pivot because
they are sequentially dependent, while everything inside a phase is
embarrassingly parallel over tiles.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..minplus.kernel import minplus_tile


def _inblock_fw(d: jnp.ndarray) -> jnp.ndarray:
    """Floyd–Warshall over one (bk,bk) tile. Pivot column and row k are
    picked with an iota mask and a min-reduce rather than ``d[:, k]``:
    Mosaic refuses a dynamic lane index, and the masked min returns the
    same element bit for bit (every other lane is +inf)."""
    lane = jax.lax.broadcasted_iota(jnp.int32, d.shape, 1)
    sub = jax.lax.broadcasted_iota(jnp.int32, d.shape, 0)

    def body(k, d):
        col = jnp.min(jnp.where(lane == k, d, jnp.inf), axis=1,
                      keepdims=True)
        row = jnp.min(jnp.where(sub == k, d, jnp.inf), axis=0,
                      keepdims=True)
        return jnp.minimum(d, col + row)
    return jax.lax.fori_loop(0, d.shape[0], body, d)


def _phase1_kernel(d_ref, o_ref):
    o_ref[...] = _inblock_fw(d_ref[...])


def _phase2_row_kernel(pivot_ref, row_ref, o_ref):
    # D[kb, j] = min(D[kb, j], pivot ⊗ D[kb, j])
    o_ref[...] = minplus_tile(pivot_ref, row_ref, row_ref[...])


def _phase2_col_kernel(pivot_ref, col_ref, o_ref):
    # D[i, kb] = min(D[i, kb], D[i, kb] ⊗ pivot)
    o_ref[...] = minplus_tile(col_ref, pivot_ref, col_ref[...])


def _phase3_kernel(col_ref, row_ref, d_ref, o_ref):
    # D[i, j] = min(D[i, j], D[i, kb] ⊗ D[kb, j])
    o_ref[...] = minplus_tile(col_ref, row_ref, d_ref[...])


@functools.partial(jax.jit, static_argnames=("bk", "interpret"))
def floyd_warshall_pallas(adj: jnp.ndarray, *, bk: int = 128,
                          interpret: bool = False) -> jnp.ndarray:
    """Exact dense APSP; input inf-padded to a multiple of ``bk``."""
    n = adj.shape[0]
    d = jnp.minimum(adj.astype(jnp.float32),
                    jnp.where(jnp.eye(n, dtype=bool), 0.0, jnp.inf))
    pad = (-n) % bk
    if pad:
        d = jnp.pad(d, ((0, pad), (0, pad)), constant_values=jnp.inf)
    npad = d.shape[0]
    nb = npad // bk

    p1 = pl.pallas_call(
        _phase1_kernel,
        grid=(1,),
        in_specs=[pl.BlockSpec((bk, bk), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((bk, bk), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((bk, bk), jnp.float32),
        interpret=interpret,
    )

    def p2_row(pivot, row):
        return pl.pallas_call(
            _phase2_row_kernel,
            grid=(row.shape[1] // bk,),
            in_specs=[pl.BlockSpec((bk, bk), lambda j: (0, 0)),
                      pl.BlockSpec((bk, bk), lambda j: (0, j))],
            out_specs=pl.BlockSpec((bk, bk), lambda j: (0, j)),
            out_shape=jax.ShapeDtypeStruct(row.shape, jnp.float32),
            interpret=interpret,
        )(pivot, row)

    def p2_col(pivot, col):
        return pl.pallas_call(
            _phase2_col_kernel,
            grid=(col.shape[0] // bk,),
            in_specs=[pl.BlockSpec((bk, bk), lambda i: (0, 0)),
                      pl.BlockSpec((bk, bk), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((bk, bk), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct(col.shape, jnp.float32),
            interpret=interpret,
        )(pivot, col)

    def p3(col, row, rest):
        return pl.pallas_call(
            _phase3_kernel,
            grid=(rest.shape[0] // bk, rest.shape[1] // bk),
            in_specs=[pl.BlockSpec((bk, bk), lambda i, j: (i, 0)),
                      pl.BlockSpec((bk, bk), lambda i, j: (0, j)),
                      pl.BlockSpec((bk, bk), lambda i, j: (i, j))],
            out_specs=pl.BlockSpec((bk, bk), lambda i, j: (i, j)),
            out_shape=jax.ShapeDtypeStruct(rest.shape, jnp.float32),
            interpret=interpret,
        )(col, row, rest)

    for kb in range(nb):
        lo = kb * bk
        pivot = jax.lax.dynamic_slice(d, (lo, lo), (bk, bk))
        pivot = p1(pivot)
        row = jax.lax.dynamic_update_slice(
            d[lo:lo + bk, :], pivot, (0, lo))
        row = p2_row(pivot, row)
        col = jax.lax.dynamic_update_slice(
            d[:, lo:lo + bk], pivot, (lo, 0))
        col = p2_col(pivot, col)
        rest = p3(col, row, d)
        # phase-3 also touched the pivot row/col tiles with stale inputs;
        # overwrite them with the exact phase-2 results
        d = jax.lax.dynamic_update_slice(rest, row, (lo, 0))
        d = jax.lax.dynamic_update_slice(d, col, (0, lo))
    return d[:n, :n].astype(adj.dtype)
