"""Mixture-of-Experts layer that holds a share of the routed experts:
drop-free, with routing per sample.

The router scores all ``n_experts`` routed experts in f32 and keeps the
``top_k`` best of each token. The layer holds the contiguous range
``held(cfg)`` of them (expert parallelism: the other experts live on other
chips) and computes only its own experts' part of the result, for the tokens
routed to them; what the absent experts would add is left out, and no
exchange stands in for them.

Every (token, held expert) assignment gets a row of a static buffer sized
for the worst case, B * T * min(top_k, held) rows, so nothing is ever
dropped. The rows are laid out expert by expert and, inside an expert,
sample by sample; ``gs`` (B, held) counts the rows of each (sample, expert)
group. The grouped matmuls (``jax.lax.ragged_dot``) read only the rows that
carry tokens; rows past the last group give zeros.

DP: routing is a function of each token alone and no capacity is shared, so
a sample's gradient depends on that sample alone. The expert matmuls are
``moe`` taps: a sample's gradient of expert e is the sum over the rows of
its (e, sample) group (``core.ghost.sq_norm_moe``). Their records keep what
the rows are made from, not the buffer: the up matmul's the tokens and the
token of each row, the down matmul's tap sits on the weighted combine of
its output rows. The router is an ordinary ``mm`` tap.

Profiler scopes: ``moe_dispatch`` (router, top-k, the row layout, the gather
into rows and the weighted combine) and ``moe_experts`` (the grouped
matmuls and the gate between them).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.custom_batching import custom_vmap

from repro.configs.base import ModelConfig
from repro.models import layers as L

F32 = jnp.float32


def held(cfg: ModelConfig) -> tuple:
    """-> (first, count): the routed experts this layer holds."""
    n = cfg.experts_held or cfg.n_experts
    if not 0 <= cfg.first_expert <= cfg.first_expert + n <= cfg.n_experts:
        raise ValueError(f"{cfg.name}: experts [{cfg.first_expert}, "
                         f"{cfg.first_expert + n}) are not among the "
                         f"{cfg.n_experts} routed experts")
    return cfg.first_expert, n


def moe_init(rng, cfg: ModelConfig):
    d, ff = cfg.d_model, cfg.moe_d_ff or cfg.d_ff
    _, H = held(cfg)
    dt = jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(rng, 4)
    mult = 2 if cfg.act == "swiglu" else 1
    p = {
        "router": L.linear_init(ks[0], d, cfg.n_experts, dt),
        "experts": {
            "up": {"w": L.normal_init(ks[1], (H, d, mult * ff), dt,
                                      1.0 / math.sqrt(d))},
            "down": {"w": L.normal_init(ks[2], (H, ff, d), dt,
                                        1.0 / math.sqrt(ff))},
        },
    }
    if cfg.n_shared:
        from repro.models.transformer import mlp_init  # local to avoid cycle
        p["shared"] = mlp_init(ks[3], cfg, d_ff=cfg.n_shared * ff)
    return p


# ---------------------------------------------------------- grouped matmul
# ``jax.lax.ragged_dot`` batches under vmap only with every operand mapped
# on axis 0, and custom_vmap rules cannot be differentiated in reverse
# mode; so the grouped matmul is a custom_vjp whose forward and backward
# are each a custom_vmap (under vmap: one mapped element at a time).
# Without vmap both are the plain ragged dots.
_DW = jax.lax.RaggedDotDimensionNumbers(             # (M,d) x (M,p) -> (H,d,p)
    dot_dimension_numbers=(([0], [0]), ([], [])),
    lhs_ragged_dimensions=[0], rhs_group_dimensions=[])


def _one_by_one(fn):
    """``fn`` as a custom_vmap whose rule maps over the batch one element
    at a time (unbatched operands broadcast)."""
    wrapped = custom_vmap(fn)

    @wrapped.def_vmap
    def _rule(axis_size, in_batched, *args):
        args = [a if b else jnp.broadcast_to(a, (axis_size,) + a.shape)
                for a, b in zip(args, in_batched)]
        return jax.lax.map(lambda xs: fn(*xs), tuple(args)), True

    return wrapped


_gmm = _one_by_one(lambda x, w, n: jax.lax.ragged_dot(x, w, n))
# the input cotangent multiplies by each expert's transposed weight; a TPU
# runs a ragged dot that contracts the weight's last dim as a dense masked
# product over every group, so the weight is transposed first
_gmm_dx = _one_by_one(lambda ct, w, n: jax.lax.ragged_dot(
    ct, jnp.swapaxes(w, 1, 2), n))
_gmm_dw = _one_by_one(lambda x, ct, n: jax.lax.ragged_dot_general(
    x, ct, n, _DW, preferred_element_type=F32))


@jax.custom_vjp
def grouped_matmul(x, w, sizes):
    """x (M, din) @ w (H, din, dout): the first sizes[0] rows by w[0], the
    next sizes[1] by w[1], ...; rows past them give zeros."""
    return _gmm(x, w, sizes)


def _gmm_fwd(x, w, sizes):
    return _gmm(x, w, sizes), (x, w, sizes)


def _gmm_bwd(res, ct):
    x, w, sizes = res
    return (_gmm_dx(ct, w, sizes), _gmm_dw(x, ct, sizes).astype(w.dtype),
            None)


grouped_matmul.defvjp(_gmm_fwd, _gmm_bwd)


def route(p, tape, x, cfg: ModelConfig):
    """x (B,T,d) -> (row (B,T,k) int32, weight (B,T,k) f32, gs (B,H) int32):
    the buffer row of each of a token's top-k experts (M, one past the
    buffer, where the expert is not held), the router's score for it (0
    where not held), and the rows of each (sample, held expert) group."""
    B, T, _ = x.shape
    first, H = held(cfg)
    M = B * T * min(cfg.top_k, H)
    logits = jnp.einsum("btd,de->bte", x, p["router"]["w"],
                        preferred_element_type=F32)
    logits = tape.record("router", "mm", logits, x)
    probs = jax.nn.softmax(logits, axis=-1)
    topv, topi = jax.lax.top_k(probs, cfg.top_k)                  # (B,T,k)
    if cfg.renorm_topk:
        topv = topv / jnp.sum(topv, axis=-1, keepdims=True)
    e = topi - first
    mine = (e >= 0) & (e < H)
    e = jnp.where(mine, e, 0)
    # a token picks each expert at most once, so (B,T,H) holds 0 or 1
    sel = jnp.sum(jax.nn.one_hot(e, H, dtype=jnp.int32)
                  * mine[..., None].astype(jnp.int32), axis=2)
    rank = jnp.cumsum(sel, axis=1) - sel             # rows of (b, e) before t
    gs = jnp.sum(sel, axis=1)                                     # (B, H)
    sizes = gs.T.reshape(-1)                        # expert-major, then sample
    start = (jnp.cumsum(sizes) - sizes).reshape(H, B).T              # (B, H)
    b_ix = jnp.arange(B)[:, None, None]
    t_ix = jnp.arange(T)[None, :, None]
    row = start[b_ix, e] + rank[b_ix, t_ix, e]
    row = jnp.where(mine, row, M)
    return row, jnp.where(mine, topv, 0.0), gs


def moe_apply(p, tape, x, cfg: ModelConfig):
    """x (B,T,d) -> (B,T,d): the held experts' part plus the shared
    experts."""
    B, T, d = x.shape
    _, H = held(cfg)
    M = B * T * min(cfg.top_k, H)
    xs = x.reshape(B * T, d)
    with jax.named_scope("moe_dispatch"):
        row, weight, gs = route(p, tape, x, cfg)
        # token and router weight of each buffer row; B*T (no token) and 0
        # on the rows left empty
        slot_tok = jnp.repeat(jnp.arange(B * T, dtype=jnp.int32), cfg.top_k)
        tok = jnp.full((M,), B * T, jnp.int32).at[row.reshape(-1)].set(
            slot_tok, mode="drop")
        row_w = jnp.zeros((M,), F32).at[row.reshape(-1)].set(
            weight.reshape(-1), mode="drop")
        rows = jnp.take(xs, tok, axis=0, mode="fill", fill_value=0)

    sizes = jnp.sum(gs, axis=0)
    ep = p["experts"]
    with jax.named_scope("moe_experts"), tape.scope("experts"):
        u = grouped_matmul(rows, ep["up"]["w"], sizes)
        # the record keeps the tokens and which token each row is, not
        # the gathered rows
        u = tape.record("up", "moe", u, {"a": xs, "rows": tok, "gs": gs})
        if cfg.act == "swiglu":
            g, u = jnp.split(u, 2, axis=-1)
            h = jax.nn.silu(g) * u
        else:
            h = jax.nn.gelu(u)
        out = grouped_matmul(h, ep["down"]["w"], sizes)

    with jax.named_scope("moe_dispatch"):
        picked = jnp.take(out, row, axis=0, mode="fill", fill_value=0)
        y = jnp.sum(picked * weight[..., None], axis=2)           # (B,T,d)
    with jax.named_scope("moe_experts"), tape.scope("experts"):
        # the down matmul's tap sits on the weighted combine: a row's
        # output cotangent is its weight times its token's, so the (B,T,d)
        # cotangent stands for the (M,d) one
        y = tape.record("down", "moe", y,
                        {"a": h, "out": tok, "w": row_w, "gs": gs})

    if cfg.n_shared:
        from repro.models.transformer import mlp_apply
        with tape.scope("shared"):
            y = y + mlp_apply(p["shared"], tape, x, cfg)
    return y.astype(x.dtype)
