"""Fused ghost-norm Pallas kernel (TPU): per-sample squared gradient norms

    n_b = sum_l sum_{t,t'} (a_lbt . a_lbt') (g_lbt . g_lbt')

computed tile-by-tile in VMEM, **never materializing the (B,T,T) Gram
matrices in HBM** — this removes the paper's 2BT^2 space term (Table 3,
module 3) entirely.

Grid (B, L, tri(nt)): the (i, j) tile pairs are enumerated over a *packed
lower triangle* — a scalar-prefetched (ntri, 2) index table drives the block
index maps, so only the j <= i tiles are ever fetched (off-diagonal tiles
count twice by symmetry). The old square grid fetched all nt^2 tile pairs
and discarded half behind ``pl.when(j <= i)``; packing the triangle halves
the HBM traffic of the norm pass. Stacked (L, B, T, d) records run as ONE
kernel launch via the L grid axis (out[b] accumulates across layers).

Beyond-paper: the paper's GhostClip/BK stores both Grams (2BT^2 floats).
Here VMEM holds 2*bt*(d+p) + 2*bt^2 floats per step.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.layout import scalar_out, scalar_rows

F32 = jnp.float32


@functools.lru_cache(maxsize=None)
def tri_table(nt: int) -> np.ndarray:
    """Packed lower-triangle enumeration: (ntri, 2) int32 with j <= i."""
    return np.array([(i, j) for i in range(nt) for j in range(i + 1)],
                    dtype=np.int32)


def _kernel(ij_ref, ai_ref, aj_ref, gi_ref, gj_ref, out_ref):
    l = pl.program_id(1)
    k = pl.program_id(2)

    @pl.when((l == 0) & (k == 0))
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    ai = ai_ref[0, 0].astype(F32)           # (bt, d)
    aj = aj_ref[0, 0].astype(F32)
    gi = gi_ref[0, 0].astype(F32)           # (bt, p)
    gj = gj_ref[0, 0].astype(F32)
    gram_a = jax.lax.dot_general(ai, aj, (((1,), (1,)), ((), ())),
                                 preferred_element_type=F32)
    gram_g = jax.lax.dot_general(gi, gj, (((1,), (1,)), ((), ())),
                                 preferred_element_type=F32)
    contrib = jnp.sum(gram_a * gram_g)
    scale = jnp.where(ij_ref[k, 0] == ij_ref[k, 1], 1.0, 2.0)
    out_ref[...] += scale * contrib


@functools.partial(jax.jit, static_argnames=("block_t", "interpret"))
def ghost_norm(a, ds, block_t: int = 128, interpret: bool = False):
    """a (L,B,T,d) or (B,T,d), ds likewise -> per-sample sq norms (B,) f32."""
    if a.ndim == 3:
        a, ds = a[None], ds[None]
    L, B, T, d = a.shape
    p = ds.shape[-1]
    bt = min(block_t, T)
    if T % bt:
        pad = bt - T % bt
        a = jnp.pad(a, ((0, 0), (0, 0), (0, pad), (0, 0)))
        ds = jnp.pad(ds, ((0, 0), (0, 0), (0, pad), (0, 0)))
        T = a.shape[2]
    nt = T // bt
    ij = jnp.asarray(tri_table(nt))
    ntri = ij.shape[0]
    out_spec, out_shape = scalar_out(B, lambda b, l, k, ij: b)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, L, ntri),
        in_specs=[
            pl.BlockSpec((1, 1, bt, d), lambda b, l, k, ij: (l, b, ij[k, 0], 0)),
            pl.BlockSpec((1, 1, bt, d), lambda b, l, k, ij: (l, b, ij[k, 1], 0)),
            pl.BlockSpec((1, 1, bt, p), lambda b, l, k, ij: (l, b, ij[k, 0], 0)),
            pl.BlockSpec((1, 1, bt, p), lambda b, l, k, ij: (l, b, ij[k, 1], 0)),
        ],
        out_specs=out_spec,
    )
    return scalar_rows(pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        interpret=interpret,
        name="ghost_norm",
    )(ij, a, a, ds, ds))
