"""Fused clipped embedding-gradient Pallas kernel (TPU): BK line 9 for an
embedding lookup,

    G_l[v] = sum_b C_b sum_t 1[id_lbt == v] ds_lbt        -> (L, V, d)

i.e. a clip-weighted scatter-add of the cotangents into vocab rows. The jnp
path materializes the (B,T,d) intermediate C*ds in HBM and then scatter-adds
it; here the vocab axis is tiled and each (bv, d) output tile is accumulated
in VMEM across samples: the tile membership one-hot 1[id == v0+arange(bv)]
is built in-register from the id tile and contracted against the cotangents
on the MXU with the clip factor fused in — no weighted copy, no HBM one-hot,
and each output row is written exactly once.

Grid (L, V/bv, B), B innermost. Cost note: the cotangents are re-read once
per vocab tile, so bv should be as large as VMEM allows (dispatch picks it);
the scatter alternative (sequential dynamic-indexed row updates) cannot keep
a V*d output resident in VMEM for real vocabularies.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.layout import scalar_in_spec

F32 = jnp.float32


def _kernel(ids_ref, g_ref, c_ref, out_ref):
    b = pl.program_id(2)

    @pl.when(b == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    bv = out_ref.shape[1]
    v0 = pl.program_id(1) * bv
    ids = ids_ref[0, 0]                       # (T, 1) int, column
    g = g_ref[0, 0].astype(F32)               # (T, d)
    c = c_ref[b]                              # scalar clip factor (SMEM)
    vrange = v0 + jax.lax.broadcasted_iota(jnp.int32, (1, bv), 1)
    onehot = (ids == vrange).astype(F32)                     # (T, bv)
    tile = jax.lax.dot_general(onehot, g, (((0,), (0,)), ((), ())),
                               preferred_element_type=F32)   # (bv, d)
    out_ref[0] += c * tile


@functools.partial(jax.jit, static_argnames=("vocab", "block_v", "interpret"))
def emb_clipped_grad(ids, C, ds, vocab: int, block_v: int = 512,
                     interpret: bool = False):
    """ids (L,B,T) or (B,T) int, C (B,), ds (L,B,T,d) or (B,T,d)
    -> (L,vocab,d) or (vocab,d) f32."""
    squeeze = ids.ndim == 2
    if squeeze:
        ids, ds = ids[None], ds[None]
    L, B, T = ids.shape
    d = ds.shape[-1]
    bv = min(block_v, vocab)
    nv = pl.cdiv(vocab, bv)
    V = nv * bv  # padded vocab rows stay zero: no id can match them

    out = pl.pallas_call(
        _kernel,
        grid=(L, nv, B),
        in_specs=[
            # ids as a (T, 1) column: a (1, T) block of (B, T) is refused
            pl.BlockSpec((1, 1, T, 1), lambda l, v, b: (l, b, 0, 0)),
            pl.BlockSpec((1, 1, T, d), lambda l, v, b: (l, b, 0, 0)),
            scalar_in_spec(),
        ],
        out_specs=pl.BlockSpec((1, bv, d), lambda l, v, b: (l, v, 0)),
        out_shape=jax.ShapeDtypeStruct((L, V, d), F32),
        interpret=interpret,
        name="emb_clipped_grad",
    )(ids[..., None], ds, C.astype(F32))
    out = out[:, :vocab]
    return out[0] if squeeze else out
