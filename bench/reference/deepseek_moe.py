"""Plain float32 DeepSeekMoE decoder (arXiv:2401.06066), on one chip's share
of the routed experts.

Straight ``jax.numpy``: no kernels, no cache, no scan, no remat, no grouped
rows. One row of tokens at a time. Matrix products run at
``Precision.HIGHEST`` (a TPU runs a plain f32 product in bf16 passes
otherwise). ``lo`` rounds every matrix operand; it is the identity for the
reference and a float8 round trip for the lower-precision control.

This is the ``reference`` that the deepseek-moe configuration files name
(interface: ``reference/__init__.py``).

Layers ``first_k_dense_replace`` and up are expert layers. With h the
RMSNorm of the residual x: s = softmax(h W_r) over all routed experts (the
published ``n_routed_experts``); S = the ``num_experts_per_tok`` largest,
not renormalised unless ``norm_topk_prob``; y = sum over the HELD experts e
in S of s_e * W2_e(silu(Wg_e h) * Wu_e h), plus the shared experts, one
SwiGLU MLP of width n_shared_experts * moe_intermediate_size. The held
experts are ``n_routed_experts`` (the file's count, this chip's share) from
``first_routed_expert`` on; what the other experts would add lives on other
chips and is left out, as in the program. Each held expert is evaluated on
every token and masked, a dense loop with no buffer and nothing dropped.

The layout is the program's: fused q|k|v (no bias), SwiGLU weights with gate
and up fused (gate first), experts stacked on a leading axis, layers past
the dense ones stacked, separate embedding and LM head. The pre-training
balance loss is not part of the loss.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST

# the sizes at which the CPU tests run a deepseek-moe configuration: one
# dense layer and two expert layers, 8 of the 64 routed experts held
TINY = dict(hidden_size=32, intermediate_size=48, moe_intermediate_size=16,
            num_attention_heads=4, num_key_value_heads=4, head_dim=8,
            num_hidden_layers=3, n_routed_experts=8, vocab_size=64)


def routed(cfg: dict) -> int:
    """The router's width: every routed expert of the published model."""
    return cfg.get("published", {}).get("n_routed_experts",
                                        cfg["n_routed_experts"])


def sizes(cfg: dict) -> dict:
    return dict(d=cfg["hidden_size"], ff=cfg["intermediate_size"],
                ffe=cfg["moe_intermediate_size"],
                H=cfg["num_attention_heads"], K=cfg["num_key_value_heads"],
                hd=cfg["head_dim"], L=cfg["num_hidden_layers"],
                nd=cfg["first_k_dense_replace"], V=cfg["vocab_size"],
                E=cfg["n_routed_experts"], first=cfg["first_routed_expert"],
                R=routed(cfg), k=cfg["num_experts_per_tok"],
                sff=cfg["n_shared_experts"] * cfg["moe_intermediate_size"],
                norm_topk=cfg["norm_topk_prob"],
                theta=cfg["rope_theta"], eps=cfg["rms_norm_eps"])


def param_shapes(cfg: dict) -> dict:
    s = sizes(cfg)
    d, ff, ffe, H, K, hd = (s[k] for k in ("d", "ff", "ffe", "H", "K", "hd"))
    qkv = (H + 2 * K) * hd
    out = {}
    for i in range(s["nd"]):
        pre = f"dense0_{i}/"
        out.update({pre + "attn/o/w": (H * hd, d),
                    pre + "attn/qkv/w": (d, qkv),
                    pre + "ln1/g": (d,), pre + "ln2/g": (d,),
                    pre + "mlp/down/w": (ff, d),
                    pre + "mlp/up/w": (d, 2 * ff)})
    Lm, E, sff = s["L"] - s["nd"], s["E"], s["sff"]
    out.update({
        "blocks/attn/o/w": (Lm, H * hd, d),
        "blocks/attn/qkv/w": (Lm, d, qkv),
        "blocks/ln1/g": (Lm, d),
        "blocks/ln2/g": (Lm, d),
        "blocks/mlp/experts/down/w": (Lm, E, ffe, d),
        "blocks/mlp/experts/up/w": (Lm, E, d, 2 * ffe),
        "blocks/mlp/router/w": (Lm, d, s["R"]),
        "blocks/mlp/shared/down/w": (Lm, sff, d),
        "blocks/mlp/shared/up/w": (Lm, d, 2 * sff),
        "embed/w": (s["V"], d),
        "final_norm/g": (d,),
        "head/w": (d, s["V"]),
    })
    return out


def flops_per_token(cfg: dict, T: int) -> float:
    """PaLM (arXiv:2204.02311, app. B): 6 N + 12 L H Q T, N the parameters
    in the matrix products a token passes through: every attention and
    dense weight, the router, the shared experts, and of the held experts
    the share a token is routed to (top-k of the routed experts: 6 * 8 / 64
    = 0.75 expert evaluations per expert layer), the LM head; not the
    embedding gather. Recomputation and DP's own work are not counted."""
    s = sizes(cfg)
    n, experts = 0.0, 0.0
    for p, shape in param_shapes(cfg).items():
        if not p.endswith("/w") or p == "embed/w":
            continue
        if "/experts/" in p:
            experts += math.prod(shape)
        else:
            n += math.prod(shape)
    n += experts * s["k"] / s["R"]
    return 6.0 * n + 12.0 * (s["L"] * T * s["H"] * s["hd"])


def program_config(cfg: dict) -> tuple:
    """-> (sizes, stated): the program's ModelConfig fields at the sizes
    the file states, and the fields the arch's registered config must
    already have."""
    sizes = dict(
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        d_ff=cfg["intermediate_size"],
        moe_d_ff=cfg["moe_intermediate_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        vocab=cfg["vocab_size"], n_experts=routed(cfg),
        experts_held=cfg["n_routed_experts"],
        first_expert=cfg["first_routed_expert"])
    stated = {
        "top_k": cfg["num_experts_per_tok"],
        "n_shared": cfg["n_shared_experts"],
        "first_k_dense": cfg["first_k_dense_replace"],
        "renorm_topk": cfg["norm_topk_prob"],
        "rope_theta": cfg["rope_theta"], "qkv_bias": cfg["attention_bias"],
        "dtype": cfg["torch_dtype"], "param_dtype": cfg["torch_dtype"],
        "act": "swiglu", "norm": "rmsnorm", "family": "moe",
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


def _swiglu(h, up, down, mm):
    gate, u = jnp.split(mm(h, up), 2, axis=-1)
    return mm(jax.nn.silu(gate) * u, down)


def routed_part(h, router, up, down, s: dict, experts, mm):
    """The part of an expert layer's output that the routed experts
    ``experts`` (indices into the router's width; ``up``/``down`` hold
    their weights in that order) give: sum over those of a token's top-k
    of s_e * W2_e(silu(Wg_e h) * Wu_e h), each evaluated on every token and
    masked."""
    scores = jax.nn.softmax(mm(h, router), axis=-1)
    topv, topi = jax.lax.top_k(scores, s["k"])
    if s["norm_topk"]:
        topv = topv / jnp.sum(topv, axis=-1, keepdims=True)
    y = jnp.zeros(h.shape, F32)
    for i, e in enumerate(experts):
        gate = jnp.sum(jnp.where(topi == e, topv, 0.0), axis=-1)
        y = y + gate[:, None] * _swiglu(h, up[i], down[i], mm)
    return y


def row_loss(p: dict, tokens, cfg: dict, lo=lambda x: x):
    """Mean next-token cross entropy of one row ``tokens`` (T,) int32."""
    s = sizes(cfg)
    H, K, hd, eps = s["H"], s["K"], s["hd"], s["eps"]
    mm = lambda a, w: jnp.dot(lo(a), lo(w), precision=HIGHEST)
    T = tokens.shape[0]
    x = p["embed/w"][tokens]
    causal = jnp.tril(jnp.ones((T, T), bool))
    layers = [(f"dense0_{i}/", None) for i in range(s["nd"])]
    layers += [("blocks/", l) for l in range(s["L"] - s["nd"])]
    for pre, l in layers:
        w = (lambda n: p[pre + n]) if l is None else \
            (lambda n: p[pre + n][l])
        h = _rmsnorm(x, w("ln1/g"), eps)
        qkv = mm(h, w("attn/qkv/w"))
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
        x = x + mm(o, w("attn/o/w"))
        h = _rmsnorm(x, w("ln2/g"), eps)
        if l is None:
            x = x + _swiglu(h, w("mlp/up/w"), w("mlp/down/w"), mm)
            continue
        x = x + _swiglu(h, w("mlp/shared/up/w"), w("mlp/shared/down/w"),
                        mm) + routed_part(
            h, w("mlp/router/w"), w("mlp/experts/up/w"),
            w("mlp/experts/down/w"), s,
            range(s["first"], s["first"] + s["E"]), mm)
    x = _rmsnorm(x, p["final_norm/g"], eps)
    logits = mm(x[:-1], p["head/w"])
    gold = jnp.take_along_axis(logits, tokens[1:, None], axis=-1)[:, 0]
    return jnp.mean(jax.nn.logsumexp(logits, axis=-1) - gold)
