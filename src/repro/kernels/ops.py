"""Jit'd wrappers around the Pallas kernels, with the layout handling the DP
engine expects (stacked layer dims, padding) and automatic
interpret-mode on CPU (kernels are validated on CPU via interpret=True; TPU
v5e is the compile target). Policy — which kernel, which blocks — lives in
repro.kernels.dispatch; these wrappers are pure mechanism."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.clipped_grad import clipped_grad as _clipped_grad
from repro.kernels.fused_clip import fused_clip_grad as _fused_clip
from repro.kernels.emb_grad import emb_clipped_grad as _emb_grad
from repro.kernels.emb_norm import emb_ghost_norm as _emb_norm
from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.ghost_norm import ghost_norm as _ghost_norm
from repro.kernels.grad_norm_direct import grad_norm_direct as _direct
from repro.kernels.wkv6 import wkv6 as _wkv6


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


# ------------------------------------------------------------------ mm taps
def ghost_norm_mm(a, ds, block_t: int = 128):
    """(B,T,d)/(L,B,T,d) records -> per-sample sq norms (B,)."""
    return _ghost_norm(a, ds, block_t=block_t, interpret=_interpret())


def direct_norm_mm(a, ds, block_d: int = 256, block_p: int = 256):
    return _direct(a, ds, block_d=block_d, block_p=block_p,
                   interpret=_interpret())


def clipped_grad_mm(a, C, ds, block_d: int = 256, block_p: int = 256):
    """-> (d,p) f32, or (L,d,p) for stacked records. One launch either way."""
    return _clipped_grad(a, C, ds, block_d=block_d, block_p=block_p,
                         interpret=_interpret())


def fused_clip_grad_mm(a, ds, w, clipping: str, R: float, gamma: float):
    """One-pass norm+clip+grad for a streamed single-tap unit (scope=
    'layer'): -> (G (d,p)/(L,d,p) f32, sq_norms (B,) f32). ``w`` is the
    per-sample weight (batch-pad mask) folded into the clip factors."""
    return _fused_clip(a, ds, w, clipping=clipping, R=float(R),
                       gamma=float(gamma), interpret=_interpret())


# ----------------------------------------------------------------- emb taps
def ghost_norm_emb(ids, ds, block_t: int = 128):
    """ids (B,T)/(L,B,T) int, ds (B,T,d)/(L,B,T,d) -> (B,)."""
    return _emb_norm(ids, ds, block_t=block_t, interpret=_interpret())


def clipped_grad_emb(ids, C, ds, vocab: int, block_v: int = 512):
    """-> (vocab,d) f32, or (L,vocab,d) for stacked records."""
    return _emb_grad(ids, C, ds, vocab=vocab, block_v=block_v,
                     interpret=_interpret())


# ------------------------------------------------------------------- others
def flash_attention(q, k, v, causal=True, block_q=128, block_k=128):
    return _flash(q, k, v, causal=causal, block_q=block_q, block_k=block_k,
                  interpret=_interpret())


def wkv6(r, k, v, w, u, chunk: int = 16):
    return _wkv6(r, k, v, w, u, chunk=chunk, interpret=_interpret())
