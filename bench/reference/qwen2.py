"""Plain float32 Qwen2 / Qwen2.5 decoder (arXiv:2407.10671, 2412.15115).

Straight ``jax.numpy``: no kernels, no cache, no scan, no remat. One row of
tokens at a time. Matrix products run at ``Precision.HIGHEST`` (a TPU runs a
plain f32 product in bf16 passes otherwise). ``lo`` rounds every matrix
operand; it is the identity for the reference and a float8 round trip for
the lower-precision control.

This is the ``reference`` that the qwen2 and qwen2.5 configuration files
name (interface: ``reference/__init__.py``).

The parameter layout follows the published architecture in the stacked form
the program trains: fused q|k|v projection with bias, o without, SwiGLU with
gate|up fused (gate first), RMSNorm gains, separate embedding and LM head.
Departure from the published configs: they tie the embedding and the LM
head; here (as in the program) the two are separate leaves.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST

# the sizes at which the CPU tests run a qwen2 configuration
TINY = dict(hidden_size=32, intermediate_size=48, num_attention_heads=4,
            num_key_value_heads=2, head_dim=8, num_hidden_layers=2,
            vocab_size=64)


def sizes(cfg: dict) -> dict:
    return dict(d=cfg["hidden_size"], ff=cfg["intermediate_size"],
                H=cfg["num_attention_heads"], K=cfg["num_key_value_heads"],
                hd=cfg["head_dim"], L=cfg["num_hidden_layers"],
                V=cfg["vocab_size"], theta=cfg["rope_theta"],
                eps=cfg["rms_norm_eps"])


def param_shapes(cfg: dict) -> dict:
    s = sizes(cfg)
    d, ff, H, K, hd, L, V = (s[k] for k in ("d", "ff", "H", "K", "hd", "L",
                                            "V"))
    qkv = (H + 2 * K) * hd
    return {
        "blocks/attn/o/w": (L, H * hd, d),
        "blocks/attn/qkv/b": (L, qkv),
        "blocks/attn/qkv/w": (L, d, qkv),
        "blocks/ln1/g": (L, d),
        "blocks/ln2/g": (L, d),
        "blocks/mlp/down/w": (L, ff, d),
        "blocks/mlp/up/w": (L, d, 2 * ff),
        "embed/w": (V, d),
        "final_norm/g": (d,),
        "head/w": (d, V),
    }


def flops_per_token(cfg: dict, T: int) -> float:
    """PaLM (arXiv:2204.02311, app. B): 6 N + 12 L H Q T, N the parameters
    in matrix products (LM head in, embedding gather out), Q the head size,
    T the sequence. Recomputation and DP's own norm work are not counted."""
    n = sum(math.prod(s) for p, s in param_shapes(cfg).items()
            if p.endswith("/w") and p != "embed/w")
    return 6.0 * n + 12.0 * (cfg["num_hidden_layers"] * T
                             * cfg["num_attention_heads"] * cfg["head_dim"])


def program_config(cfg: dict) -> tuple:
    """-> (sizes, stated): the program's ModelConfig fields at the sizes
    the file states, and the fields the arch's registered config must
    already have."""
    sizes = dict(
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        d_ff=cfg["intermediate_size"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        vocab=cfg["vocab_size"])
    # the program never ties embedding and LM head: a departure the file
    # states under "assumed", mirrored by this reference
    stated = {
        "rope_theta": cfg["rope_theta"], "qkv_bias": cfg["attention_bias"],
        "dtype": cfg["torch_dtype"], "param_dtype": cfg["torch_dtype"],
        "act": "swiglu", "norm": "rmsnorm", "family": "dense",
    }
    return sizes, stated


def _rmsnorm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _rope(x, theta):
    """x (T, heads, hd): rotate the two halves of each head."""
    T, hd = x.shape[0], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
    ang = jnp.arange(T, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def row_loss(p: dict, tokens, cfg: dict, lo=lambda x: x):
    """Mean next-token cross entropy of one row ``tokens`` (T,) int32."""
    s = sizes(cfg)
    H, K, hd, eps = s["H"], s["K"], s["hd"], s["eps"]
    mm = lambda a, w: jnp.dot(lo(a), lo(w), precision=HIGHEST)
    T = tokens.shape[0]
    x = p["embed/w"][tokens]
    causal = jnp.tril(jnp.ones((T, T), bool))
    for l in range(s["L"]):
        h = _rmsnorm(x, p["blocks/ln1/g"][l], eps)
        qkv = mm(h, p["blocks/attn/qkv/w"][l]) + p["blocks/attn/qkv/b"][l]
        q = qkv[:, :H * hd].reshape(T, H, hd)
        k = qkv[:, H * hd:(H + K) * hd].reshape(T, K, hd)
        v = qkv[:, (H + K) * hd:].reshape(T, K, hd)
        q, k = _rope(q, s["theta"]), _rope(k, s["theta"])
        G = H // K                             # query head j reads kv head j // G
        qg = q.reshape(T, K, G, hd)
        sc = jnp.einsum("tkgh,skh->kgts", lo(qg), lo(k),
                        precision=HIGHEST) / jnp.sqrt(F32(hd))
        sc = jnp.where(causal, sc, -jnp.inf)
        o = jnp.einsum("kgts,skh->tkgh", lo(jax.nn.softmax(sc, axis=-1)),
                       lo(v), precision=HIGHEST).reshape(T, H * hd)
        x = x + mm(o, p["blocks/attn/o/w"][l])
        h = _rmsnorm(x, p["blocks/ln2/g"][l], eps)
        gate, up = jnp.split(mm(h, p["blocks/mlp/up/w"][l]), 2, axis=-1)
        x = x + mm(jax.nn.silu(gate) * up, p["blocks/mlp/down/w"][l])
    x = _rmsnorm(x, p["final_norm/g"], eps)
    logits = mm(x[:-1], p["head/w"])
    gold = jnp.take_along_axis(logits, tokens[1:, None], axis=-1)[:, 0]
    return jnp.mean(jax.nn.logsumexp(logits, axis=-1) - gold)
