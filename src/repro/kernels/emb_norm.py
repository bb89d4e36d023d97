"""Fused embedding ghost-norm Pallas kernel (TPU): per-sample squared
gradient norms of an embedding lookup (Li et al. 2021),

    n_b = sum_l sum_{t,t'} 1[id_lbt == id_lbt'] (ds_lbt . ds_lbt')

with the (T,T) indicator formed **in-register** from two id tiles and the
(T,T) cotangent Gram formed on the MXU — neither the (B,T,T) indicator nor
the Gram ever exists in HBM (the pure-jnp path materializes both).

Grid (B, L, tri(nt)): same packed-triangular tile enumeration as
kernels.ghost_norm (scalar-prefetched (i,j) table; off-diagonal tiles count
twice by symmetry), with stacked (L,B,T) records one kernel launch via the
L grid axis. VMEM per step: 2*bt ids + 2*bt*d cotangents + bt^2 floats.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ghost_norm import tri_table
from repro.kernels.layout import scalar_out, scalar_rows

F32 = jnp.float32


def _kernel(ij_ref, ii_ref, jj_ref, gi_ref, gj_ref, out_ref):
    l = pl.program_id(1)
    k = pl.program_id(2)

    @pl.when((l == 0) & (k == 0))
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    ii = ii_ref[0, 0]                        # (bt, 1) int ids, column
    jj = jj_ref[0, 0]                        # (1, bt) int ids, row
    gi = gi_ref[0, 0].astype(F32)            # (bt, d)
    gj = gj_ref[0, 0].astype(F32)
    eq = (ii == jj).astype(F32)              # (bt, bt) in-register
    gram_g = jax.lax.dot_general(gi, gj, (((1,), (1,)), ((), ())),
                                 preferred_element_type=F32)
    contrib = jnp.sum(eq * gram_g)
    scale = jnp.where(ij_ref[k, 0] == ij_ref[k, 1], 1.0, 2.0)
    out_ref[...] += scale * contrib


@functools.partial(jax.jit, static_argnames=("block_t", "interpret"))
def emb_ghost_norm(ids, ds, block_t: int = 128, interpret: bool = False):
    """ids (L,B,T) or (B,T) int, ds (L,B,T,d) or (B,T,d) -> (B,) f32."""
    if ids.ndim == 2:
        ids, ds = ids[None], ds[None]
    L, B, T = ids.shape
    d = ds.shape[-1]
    bt = min(block_t, T)
    if T % bt:
        pad = bt - T % bt
        # pad ids with -1: padded slots only match other padding, whose
        # cotangents are zero-padded, so they contribute exactly 0
        ids = jnp.pad(ids, ((0, 0), (0, 0), (0, pad)), constant_values=-1)
        ds = jnp.pad(ds, ((0, 0), (0, 0), (0, pad), (0, 0)))
        T = ids.shape[2]
    nt = T // bt
    ij = jnp.asarray(tri_table(nt))
    ntri = ij.shape[0]
    out_spec, out_shape = scalar_out(B, lambda b, l, k, ij: b)
    # the ids go in twice, as a column and as a row tile, so that the
    # indicator is a broadcast compare: a (1, bt) block of a (B, T) array
    # is refused (second-minor block dim 1), of (B, 1, T) it is whole
    ids_col, ids_row = ids[..., None], ids[..., None, :]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, L, ntri),
        in_specs=[
            pl.BlockSpec((1, 1, bt, 1), lambda b, l, k, ij: (l, b, ij[k, 0], 0)),
            pl.BlockSpec((1, 1, 1, bt), lambda b, l, k, ij: (l, b, 0, ij[k, 1])),
            pl.BlockSpec((1, 1, bt, d), lambda b, l, k, ij: (l, b, ij[k, 0], 0)),
            pl.BlockSpec((1, 1, bt, d), lambda b, l, k, ij: (l, b, ij[k, 1], 0)),
        ],
        out_specs=out_spec,
    )
    return scalar_rows(pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        interpret=interpret,
        name="emb_ghost_norm",
    )(ij, ids_col, ids_row, ds, ds))
