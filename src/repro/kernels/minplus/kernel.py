"""Min-plus (tropical) matmul as a Pallas TPU kernel.

TPU mapping: the MXU only accelerates ring matmuls, so min-plus runs on the
VPU — the kernel streams (bm,bk)/(bk,bn) VMEM tiles and accumulates a
(bm,bn) tile with 8-wide contraction chunks (matching the 8x128 VREG
shape). The K grid axis is innermost so the output tile is revisited in a
contiguous run, and +inf is the semiring zero so block padding is free.

``relax=True`` fuses the Bellman-Ford carry ``min(D, D⊗A)`` by seeding the
accumulator with the D output-tile instead of +inf — one fewer HBM round
trip per sweep, which matters because the relaxation is memory-bound.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_CHUNK = 8  # contraction chunk = VREG sublane count


def minplus_tile(a_ref, b_ref, acc: jnp.ndarray) -> jnp.ndarray:
    """min(acc, A ⊗ B) over one (bm,bk)x(bk,bn) VMEM tile pair.

    The contraction is a static, unrolled loop over 8-wide chunks read
    straight from the refs: static slice bounds are what Mosaic can
    lower (a ``dynamic_slice`` inside a ``fori_loop`` body is refused)."""
    for c in range(a_ref.shape[1] // _CHUNK):
        lo, hi = c * _CHUNK, (c + 1) * _CHUNK
        ak = a_ref[:, lo:hi]                  # (bm, CHUNK)
        bk = b_ref[lo:hi, :]                  # (CHUNK, bn)
        # (bm, CHUNK, bn) broadcast, reduced immediately
        acc = jnp.minimum(acc, jnp.min(ak[:, :, None] + bk[None, :, :],
                                       axis=1))
    return acc


def _minplus_kernel(a_ref, b_ref, o_ref):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.full_like(o_ref[...], jnp.inf)

    o_ref[...] = minplus_tile(a_ref, b_ref, o_ref[...])


def _relax_kernel(d_ref, a_ref, carry_ref, o_ref):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = carry_ref[...]       # seed with D tile: fuses min(D, .)

    o_ref[...] = minplus_tile(d_ref, a_ref, o_ref[...])


def _pad_to(x: jnp.ndarray, m0: int, m1: int) -> jnp.ndarray:
    p0 = (-x.shape[0]) % m0
    p1 = (-x.shape[1]) % m1
    if p0 or p1:
        x = jnp.pad(x, ((0, p0), (0, p1)), constant_values=jnp.inf)
    return x


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def minplus_pallas(a: jnp.ndarray, b: jnp.ndarray, *, bm: int = 128,
                   bn: int = 128, bk: int = 128,
                   interpret: bool = False) -> jnp.ndarray:
    """C = A ⊗ B on the (min, +) semiring. Shapes need not be aligned —
    inputs are inf-padded to block multiples."""
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    a32 = _pad_to(a.astype(jnp.float32), bm, bk)
    b32 = _pad_to(b.astype(jnp.float32), bk, bn)
    mp, kp = a32.shape
    _, np_ = b32.shape
    grid = (mp // bm, np_ // bn, kp // bk)
    out = pl.pallas_call(
        _minplus_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), jnp.float32),
        interpret=interpret,
    )(a32, b32)
    return out[:m, :n].astype(a.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def relax_pallas(d: jnp.ndarray, a: jnp.ndarray, *, bm: int = 128,
                 bn: int = 128, bk: int = 128,
                 interpret: bool = False) -> jnp.ndarray:
    """D' = min(D, D ⊗ A): one fused Bellman-Ford sweep (S,V)x(V,V)."""
    s, v = d.shape
    assert a.shape == (v, v), (d.shape, a.shape)
    d32 = _pad_to(d.astype(jnp.float32), bm, bk)
    a32 = _pad_to(a.astype(jnp.float32), bk, bn)
    sp, vp = d32.shape
    grid = (sp // bm, vp // bn, vp // bk)
    out = pl.pallas_call(
        _relax_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),   # D (contract)
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),   # A
            pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),    # D (carry)
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((sp, vp), jnp.float32),
        interpret=interpret,
    )(d32, a32, d32)
    return out[:s, :v].astype(d.dtype)
