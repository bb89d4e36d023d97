"""Fused clipped-gradient Pallas kernel (TPU): BK Algorithm 1 line 9,

    G_l = sum_b C_b * a_lb^T g_lb   =  a_l^T diag(C) g_l

with the clip factor applied in-register — avoids writing the (B,T,p)
intermediate C*ds back to HBM that the einsum formulation materializes.

Grid (L, d/bd, p/bp, B): B innermost so each (l, d, p) tile accumulates over
samples in VMEM and is written once; the leading L axis makes stacked
(L,B,T,d) records a SINGLE kernel launch (the old wrapper re-launched the
kernel through jax.vmap once per layer)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.layout import scalar_in_spec

F32 = jnp.float32


def _kernel(a_ref, g_ref, c_ref, out_ref):
    b = pl.program_id(3)

    @pl.when(b == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    a = a_ref[0, 0].astype(F32)               # (T, bd)
    g = g_ref[0, 0].astype(F32)               # (T, bp)
    c = c_ref[b]                              # scalar clip factor (SMEM)
    tile = jax.lax.dot_general(a * c, g, (((0,), (0,)), ((), ())),
                               preferred_element_type=F32)
    out_ref[0] += tile


@functools.partial(jax.jit, static_argnames=("block_d", "block_p", "interpret"))
def clipped_grad(a, C, ds, block_d: int = 256, block_p: int = 256,
                 interpret: bool = False):
    """a (L,B,T,d) or (B,T,d), C (B,), ds likewise -> (L,d,p) or (d,p) f32."""
    squeeze = a.ndim == 3
    if squeeze:
        a, ds = a[None], ds[None]
    L, B, T, d = a.shape
    p = ds.shape[-1]
    bd, bp = min(block_d, d), min(block_p, p)
    pd_, pp_ = (bd - d % bd) % bd, (bp - p % bp) % bp
    if pd_:
        a = jnp.pad(a, ((0, 0), (0, 0), (0, 0), (0, pd_)))
    if pp_:
        ds = jnp.pad(ds, ((0, 0), (0, 0), (0, 0), (0, pp_)))
    D, P = a.shape[-1], ds.shape[-1]

    out = pl.pallas_call(
        _kernel,
        grid=(L, D // bd, P // bp, B),
        in_specs=[
            pl.BlockSpec((1, 1, T, bd), lambda l, i, j, b: (l, b, 0, i)),
            pl.BlockSpec((1, 1, T, bp), lambda l, i, j, b: (l, b, 0, j)),
            scalar_in_spec(),
        ],
        out_specs=pl.BlockSpec((1, bd, bp), lambda l, i, j, b: (l, i, j)),
        out_shape=jax.ShapeDtypeStruct((L, D, P), F32),
        interpret=interpret,
        name="clipped_grad",
    )(a, ds, C.astype(F32))
    out = out[:, :d, :p]
    return out[0] if squeeze else out
