"""Fused MoE ghost/direct-norm and clipped-grad Pallas kernels (TPU) over the
per-(sample, expert) capacity layout (models.moe):

    a (B,E,C,d)  mask (B,E,C)  ds (B,E,C,p)     stacked: leading L axis

The slot-validity mask is applied **in-register** to the cotangent tile, so
neither the masked copies nor the (B,E,C,C) Grams / (B,E,d,p) per-sample
expert grads ever exist in HBM (the pure-jnp path materializes all three).
Beyond-paper extension — the paper never treats MoE; this carries its
module 3/4/5 fusion to the expert-parallel layout.

  moe_ghost_norm    n_b = sum_{l,e} <am am^T, dm dm^T>_F     grid (B, L, E)
  moe_direct_norm   n_b = sum_{l,e} ||a_e^T dm_e||_F^2       grid (B,L,E,nd,np)
  moe_clipped_grad  G_le = sum_b C_b a_be^T dm_be            grid (L,E,nd,np,B)

Capacity C is small by construction (T * capacity_factor * top_k / E), so the
(C,*) blocks are kept whole; only d/p are tiled. The mask goes in as a
(C, 1) column per (sample, expert): a (1, C) block of (..., E, C) is refused
by the TPU lowering (second-minor block dim 1 of E).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.layout import scalar_in_spec, scalar_out, scalar_rows

F32 = jnp.float32


def _moe5(a, mask, ds):
    if a.ndim == 4:
        return a[None], mask[None, ..., None], ds[None], True
    if a.ndim == 5:
        return a, mask[..., None], ds, False
    raise ValueError(f"moe record must be 4D or 5D, got {a.shape}")


# ------------------------------------------------------------- ghost norm
def _ghost_kernel(a_ref, m_ref, g_ref, out_ref):
    l = pl.program_id(1)
    e = pl.program_id(2)

    @pl.when((l == 0) & (e == 0))
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    m = m_ref[0, 0, 0].astype(F32)                    # (C, 1)
    am = a_ref[0, 0, 0].astype(F32) * m               # (C, d)
    dm = g_ref[0, 0, 0].astype(F32) * m               # (C, p)
    gram_a = jax.lax.dot_general(am, am, (((1,), (1,)), ((), ())),
                                 preferred_element_type=F32)
    gram_g = jax.lax.dot_general(dm, dm, (((1,), (1,)), ((), ())),
                                 preferred_element_type=F32)
    out_ref[...] += jnp.sum(gram_a * gram_g)


@functools.partial(jax.jit, static_argnames=("interpret",))
def moe_ghost_norm(a, mask, ds, interpret: bool = False):
    """a (B,E,C,d)/(L,B,E,C,d), mask (...,E,C), ds (...,E,C,p) -> (B,) f32."""
    a, mask, ds, _ = _moe5(a, mask, ds)
    L, B, E, C, d = a.shape
    p = ds.shape[-1]
    out_spec, out_shape = scalar_out(B, lambda b, l, e: b)
    out = pl.pallas_call(
        _ghost_kernel,
        grid=(B, L, E),
        in_specs=[
            pl.BlockSpec((1, 1, 1, C, d), lambda b, l, e: (l, b, e, 0, 0)),
            pl.BlockSpec((1, 1, 1, C, 1), lambda b, l, e: (l, b, e, 0, 0)),
            pl.BlockSpec((1, 1, 1, C, p), lambda b, l, e: (l, b, e, 0, 0)),
        ],
        out_specs=out_spec,
        out_shape=out_shape,
        interpret=interpret,
        name="moe_ghost_norm",
    )(a, mask, ds)
    return scalar_rows(out)


# ------------------------------------------------------------ direct norm
def _direct_kernel(a_ref, m_ref, g_ref, out_ref):
    l = pl.program_id(1)
    e = pl.program_id(2)
    i = pl.program_id(3)
    j = pl.program_id(4)

    @pl.when((l == 0) & (e == 0) & (i == 0) & (j == 0))
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    m = m_ref[0, 0, 0].astype(F32)                    # (C, 1)
    a = a_ref[0, 0, 0].astype(F32)                    # (C, bd)
    dm = g_ref[0, 0, 0].astype(F32) * m               # (C, bp)
    tile = jax.lax.dot_general(a, dm, (((0,), (0,)), ((), ())),
                               preferred_element_type=F32)
    out_ref[...] += jnp.sum(tile * tile)


@functools.partial(jax.jit, static_argnames=("block_d", "block_p", "interpret"))
def moe_direct_norm(a, mask, ds, block_d: int = 256, block_p: int = 256,
                    interpret: bool = False):
    """Per-(sample, expert) instantiated-grad norm, summed over experts."""
    a, mask, ds, _ = _moe5(a, mask, ds)
    L, B, E, C, d = a.shape
    p = ds.shape[-1]
    bd, bp = min(block_d, d), min(block_p, p)
    if d % bd:
        a = jnp.pad(a, ((0, 0),) * 4 + ((0, bd - d % bd),))
        d = a.shape[-1]
    if p % bp:
        ds = jnp.pad(ds, ((0, 0),) * 4 + ((0, bp - p % bp),))
        p = ds.shape[-1]
    out_spec, out_shape = scalar_out(B, lambda b, l, e, i, j: b)
    out = pl.pallas_call(
        _direct_kernel,
        grid=(B, L, E, d // bd, p // bp),
        in_specs=[
            pl.BlockSpec((1, 1, 1, C, bd),
                         lambda b, l, e, i, j: (l, b, e, 0, i)),
            pl.BlockSpec((1, 1, 1, C, 1),
                         lambda b, l, e, i, j: (l, b, e, 0, 0)),
            pl.BlockSpec((1, 1, 1, C, bp),
                         lambda b, l, e, i, j: (l, b, e, 0, j)),
        ],
        out_specs=out_spec,
        out_shape=out_shape,
        interpret=interpret,
        name="moe_direct_norm",
    )(a, mask, ds)
    return scalar_rows(out)


# ----------------------------------------------------------- clipped grad
def _grad_kernel(a_ref, m_ref, g_ref, c_ref, out_ref):
    b = pl.program_id(4)

    @pl.when(b == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    m = m_ref[0, 0, 0].astype(F32)                    # (C, 1)
    a = a_ref[0, 0, 0].astype(F32)                    # (C, bd)
    dm = g_ref[0, 0, 0].astype(F32) * m               # (C, bp)
    c = c_ref[b]                                      # clip factor (SMEM)
    tile = jax.lax.dot_general(a * c, dm, (((0,), (0,)), ((), ())),
                               preferred_element_type=F32)
    out_ref[0, 0] += tile


@functools.partial(jax.jit, static_argnames=("block_d", "block_p", "interpret"))
def moe_clipped_grad(a, mask, C, ds, block_d: int = 256, block_p: int = 256,
                     interpret: bool = False):
    """-> (E,d,p) f32, or (L,E,d,p) for stacked records. One launch."""
    a, mask, ds, squeeze = _moe5(a, mask, ds)
    L, B, E, Cap, d = a.shape
    p = ds.shape[-1]
    bd, bp = min(block_d, d), min(block_p, p)
    pd_, pp_ = (bd - d % bd) % bd, (bp - p % bp) % bp
    if pd_:
        a = jnp.pad(a, ((0, 0),) * 4 + ((0, pd_),))
    if pp_:
        ds = jnp.pad(ds, ((0, 0),) * 4 + ((0, pp_),))
    D, P = a.shape[-1], ds.shape[-1]
    out = pl.pallas_call(
        _grad_kernel,
        grid=(L, E, D // bd, P // bp, B),
        in_specs=[
            pl.BlockSpec((1, 1, 1, Cap, bd),
                         lambda l, e, i, j, b: (l, b, e, 0, i)),
            pl.BlockSpec((1, 1, 1, Cap, 1),
                         lambda l, e, i, j, b: (l, b, e, 0, 0)),
            pl.BlockSpec((1, 1, 1, Cap, bp),
                         lambda l, e, i, j, b: (l, b, e, 0, j)),
            scalar_in_spec(),
        ],
        out_specs=pl.BlockSpec((1, 1, bd, bp),
                               lambda l, e, i, j, b: (l, e, i, j)),
        out_shape=jax.ShapeDtypeStruct((L, E, D, P), F32),
        interpret=interpret,
        name="moe_clipped_grad",
    )(a, mask, ds, C.astype(F32))
    out = out[:, :, :d, :p]
    return out[0] if squeeze else out
