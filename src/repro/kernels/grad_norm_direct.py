"""Fused direct-norm Pallas kernel (TPU): per-sample squared gradient norms
via instantiation,

    n_b = sum_l || a_lb^T g_lb ||_F^2

computed (d,p)-tile by tile **without materializing the (B,d,p) per-sample
gradients in HBM** — removes the Bpd space term of module 4 (the reason
Opacus "cannot scale to large models"), so the MixOpt hybrid decision becomes
a pure time tradeoff.

Grid (B, L, d/bd, p/bp): stacked (L,B,T,d) records run as ONE kernel launch
via the L grid axis — out[b] stays resident while every (layer, tile) pair
accumulates into it."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.layout import scalar_out, scalar_rows

F32 = jnp.float32


def _kernel(a_ref, g_ref, out_ref):
    l = pl.program_id(1)
    i = pl.program_id(2)
    j = pl.program_id(3)

    @pl.when((l == 0) & (i == 0) & (j == 0))
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    a = a_ref[0, 0].astype(F32)              # (T, bd)
    g = g_ref[0, 0].astype(F32)              # (T, bp)
    tile = jax.lax.dot_general(a, g, (((0,), (0,)), ((), ())),
                               preferred_element_type=F32)  # (bd, bp)
    out_ref[...] += jnp.sum(tile * tile)


@functools.partial(jax.jit, static_argnames=("block_d", "block_p", "interpret"))
def grad_norm_direct(a, ds, block_d: int = 256, block_p: int = 256,
                     interpret: bool = False):
    """a (L,B,T,d) or (B,T,d), ds likewise -> (B,) f32."""
    if a.ndim == 3:
        a, ds = a[None], ds[None]
    L, B, T, d = a.shape
    p = ds.shape[-1]
    bd, bp = min(block_d, d), min(block_p, p)
    if d % bd:
        a = jnp.pad(a, ((0, 0), (0, 0), (0, 0), (0, bd - d % bd)))
        d = a.shape[-1]
    if p % bp:
        ds = jnp.pad(ds, ((0, 0), (0, 0), (0, 0), (0, bp - p % bp)))
        p = ds.shape[-1]
    out_spec, out_shape = scalar_out(B, lambda b, l, i, j: b)

    out = pl.pallas_call(
        _kernel,
        grid=(B, L, d // bd, p // bp),
        in_specs=[
            pl.BlockSpec((1, 1, T, bd), lambda b, l, i, j: (l, b, 0, i)),
            pl.BlockSpec((1, 1, T, bp), lambda b, l, i, j: (l, b, 0, j)),
        ],
        out_specs=out_spec,
        out_shape=out_shape,
        interpret=interpret,
        name="grad_norm_direct",
    )(a, ds)
    return scalar_rows(out)
