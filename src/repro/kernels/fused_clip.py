"""Fused norm+clip+grad Pallas kernel (TPU): the one-pass form of BK
Algorithm 1 lines 6-9 for a SINGLE-TAP clip unit (scope='layer'),

    g_b  = a_b^T ds_b          per-sample gradient        (L,d,p)
    n_b  = ||g_b||_F           per-sample norm            scalar
    C_b  = clip(n_b) * w_b     clip factor x batch mask   scalar
    G   += C_b * g_b           clipped weighted grad      (L,d,p)

in ONE grid pass over the batch: per grid step the whole per-sample
gradient lives in VMEM, the norm and clip factor are computed in-register,
and the weighted tile folds straight into the output accumulator. The
contraction a^T ds runs ONCE — this is the mixopt book-keeping trick
(cache the per-sample grad between the norm and weighting passes) without
the HBM cache, possible exactly because a layer-scope unit's clip decision
closes over this one tap.

Grid (B,): the leading L axis keeps stacked (L,B,T,d) records a single
launch, and the (L,d,p) working set is what the dispatch cost model
(``fused_plan``) checks against the VMEM budget before routing here.

Outputs: (G (L,d,p) f32, sq (B,) f32) — the per-sample SQUARED norms are
emitted too so the engine's norm telemetry / flat-vs-layer diagnostics see
the same numbers as the two-phase path.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.layout import scalar_in_spec, scalar_out, scalar_rows

F32 = jnp.float32


def _kernel(a_ref, g_ref, w_ref, out_ref, sq_ref, *, clip):
    b = pl.program_id(0)

    @pl.when(b == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    a = a_ref[:, 0].astype(F32)               # (L, T, d)
    ds = g_ref[:, 0].astype(F32)              # (L, T, p)
    # batched over L, contract T: per-sample grad for the WHOLE stacked unit
    g = jax.lax.dot_general(a, ds, (((1,), (1,)), ((0,), (0,))),
                            preferred_element_type=F32)     # (L, d, p)
    sq = jnp.sum(g * g)
    c = clip(jnp.sqrt(sq)).astype(F32) * w_ref[b]
    sq_ref[...] = jnp.broadcast_to(sq, sq_ref.shape)
    out_ref[...] += c * g


@functools.partial(jax.jit,
                   static_argnames=("clipping", "R", "gamma", "interpret"))
def fused_clip_grad(a, ds, w, clipping: str, R: float, gamma: float,
                    interpret: bool = False):
    """a (L,B,T,d) or (B,T,d), ds likewise (last dim p), w (B,) per-sample
    weight (batch-pad mask) -> (G (L,d,p) or (d,p) f32, sq (B,) f32).

    ``clipping``/``R``/``gamma`` are static and build the clip fn via
    :func:`repro.core.clipping.get_clip_fn` — it runs on a scalar inside
    the kernel body (jnp scalar ops lower fine under Pallas)."""
    from repro.core.clipping import get_clip_fn
    kw = {"gamma": gamma} if clipping == "automatic" else {}
    clip = get_clip_fn(clipping, R, **kw)

    squeeze = a.ndim == 3
    if squeeze:
        a, ds = a[None], ds[None]
    L, B, T, d = a.shape
    p = ds.shape[-1]
    # lane-align the contraction dims; zero pads are norm/grad-neutral
    pd_, pp_ = (-d) % 128, (-p) % 128
    if pd_:
        a = jnp.pad(a, ((0, 0), (0, 0), (0, 0), (0, pd_)))
    if pp_:
        ds = jnp.pad(ds, ((0, 0), (0, 0), (0, 0), (0, pp_)))
    D, P = a.shape[-1], ds.shape[-1]
    sq_spec, sq_shape = scalar_out(B, lambda b: b)

    out, sq = pl.pallas_call(
        functools.partial(_kernel, clip=clip),
        grid=(B,),
        in_specs=[
            pl.BlockSpec((L, 1, T, D), lambda b: (0, b, 0, 0)),
            pl.BlockSpec((L, 1, T, P), lambda b: (0, b, 0, 0)),
            scalar_in_spec(),
        ],
        out_specs=[
            pl.BlockSpec((L, D, P), lambda b: (0, 0, 0)),
            sq_spec,
        ],
        out_shape=[
            jax.ShapeDtypeStruct((L, D, P), F32),
            sq_shape,
        ],
        interpret=interpret,
        name="fused_clip_grad",
    )(a, ds, w.astype(F32))
    out = out[:, :d, :p]
    return (out[0] if squeeze else out), scalar_rows(sq)
