"""The expert layer that holds a share of the routed experts (models/moe.py)
and its BK taps (core/ghost.py ``moe``):

  * the layer equals a plain per-token loop over its held experts, f32;
  * the shares of a layer cut into parts, the shared experts counted once,
    add up to the uncut layer;
  * drop-free: a router that sends every token of a sample to the same
    held experts fills the buffer and still matches the loop;
  * the grouped matmul's gradients, under vmap too, are the dense ones;
  * BK's per-sample norms and clipped sums under deepseek-moe-16b's
    three-group policy equal per-row ``jax.grad`` -> clip -> sum;
  * the grouped norms and clipped sums, with every record form and column
    chunking, equal the per-(expert, sample) gradients made one by one;
  * the profiler scopes reach the compiled step's op names.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import build, get_config, get_policy
from repro.core import ghost
from repro.core.bk import DPConfig, bk_clipped_sum
from repro.core.engine import make_grad_fn
from repro.core.policy import resolve_policy
from repro.core.tape import Tape
from repro.models import moe as M
from repro.utils.tree import flatten

F32 = jnp.float32


def _cfg(**kw):
    """deepseek-moe-16b's layer at a CPU size: 16 routed experts, top 6."""
    base = dict(n_layers=3, d_model=16, n_heads=2, n_kv_heads=2, head_dim=8,
                d_ff=24, moe_d_ff=8, vocab=32, n_experts=16, experts_held=16,
                max_t=32, dtype="float32", param_dtype="float32",
                remat=False, attn_chunk=0)
    base.update(kw)
    return get_config("deepseek-moe-16b").with_(**base)


def _layer_params(cfg, seed=0, router_scale=1.0):
    p = M.moe_init(jax.random.PRNGKey(seed), cfg)
    p["router"]["w"] = p["router"]["w"] * router_scale
    return p


def _loop(p, x, cfg):
    """The layer by its definition: each held expert on every token,
    weighted by the token's router score where it is among the top k."""
    first, H = M.held(cfg)
    hp = jax.lax.Precision.HIGHEST
    probs = jax.nn.softmax(jnp.dot(x, p["router"]["w"], precision=hp), -1)
    topv, topi = jax.lax.top_k(probs, cfg.top_k)
    if cfg.renorm_topk:
        topv = topv / jnp.sum(topv, -1, keepdims=True)

    def swiglu(h, up, down):
        g, u = jnp.split(jnp.dot(h, up, precision=hp), 2, axis=-1)
        return jnp.dot(jax.nn.silu(g) * u, down, precision=hp)

    y = swiglu(x, p["shared"]["up"]["w"], p["shared"]["down"]["w"])
    for e in range(H):
        gate = jnp.sum(jnp.where(topi == first + e, topv, 0.0), axis=-1)
        y = y + gate[..., None] * swiglu(x, p["experts"]["up"]["w"][e],
                                         p["experts"]["down"]["w"][e])
    return y


def _x(B, T, d, seed=1):
    return jax.random.normal(jax.random.PRNGKey(seed), (B, T, d), F32)


@pytest.mark.parametrize("held,first,top_k,renorm", [
    (16, 0, 6, False), (8, 0, 6, False), (8, 8, 6, False), (4, 4, 6, False),
    (4, 0, 2, True), (2, 14, 6, False)])
def test_layer_matches_the_loop(held, first, top_k, renorm):
    cfg = _cfg(experts_held=held, first_expert=first, top_k=top_k,
               renorm_topk=renorm)
    p = _layer_params(cfg, router_scale=3.0)
    x = _x(3, 10, cfg.d_model)
    with jax.default_matmul_precision("highest"):
        got = M.moe_apply(p, Tape.null(), x, cfg)
    np.testing.assert_allclose(got, _loop(p, x, cfg), rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("shares", [2, 4, 8])
def test_shares_add_up_to_the_whole_layer(shares):
    """Each share holds 16 / shares experts and the same router and shared
    experts: the shares' routed parts, the shared experts counted once, are
    the layer that holds all 16."""
    whole_cfg = _cfg()
    whole = _layer_params(whole_cfg, router_scale=3.0)
    x = _x(2, 12, whole_cfg.d_model)
    n = whole_cfg.n_experts // shares
    no_shared = lambda cfg, p: M.moe_apply(
        {k: v for k, v in p.items() if k != "shared"}, Tape.null(), x,
        cfg.with_(n_shared=0))
    with jax.default_matmul_precision("highest"):
        want = M.moe_apply(whole, Tape.null(), x, whole_cfg)
        shared = want - no_shared(whole_cfg, whole)
        total = shared
        for i in range(shares):
            cfg = whole_cfg.with_(experts_held=n, first_expert=i * n)
            part = dict(whole, experts=jax.tree_util.tree_map(
                lambda w: w[i * n:(i + 1) * n], whole["experts"]))
            total = total + no_shared(cfg, part)
    np.testing.assert_allclose(total, want, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("B,T", [(1, 8), (3, 16)])
def test_drop_free_when_every_token_picks_the_same_experts(B, T):
    """A router that gives experts 2-7 every token's top 6: the buffer of
    B * T * 6 rows is full, and nothing is dropped."""
    cfg = _cfg(experts_held=8, first_expert=0)
    p = _layer_params(cfg)
    x = jnp.abs(_x(B, T, cfg.d_model)) + 0.1
    w = jnp.zeros_like(p["router"]["w"]).at[:, 2:8].set(
        jnp.arange(1.0, 7.0) * 0.5)
    p["router"]["w"] = w
    row, weight, gs = M.route(p, Tape.null(), x, cfg)
    assert (np.asarray(gs)[:, 2:8] == T).all() and np.asarray(gs).sum() \
        == B * T * 6
    assert sorted(np.asarray(row).reshape(-1).tolist()) == \
        list(range(B * T * 6))
    with jax.default_matmul_precision("highest"):
        got = M.moe_apply(p, Tape.null(), x, cfg)
    np.testing.assert_allclose(got, _loop(p, x, cfg), rtol=2e-5, atol=2e-6)


# --------------------------------------------------------- grouped matmul
GROUPS = [((3, 0, 4), 5, 6), ((0, 0, 5), 3, 4), ((2, 7, 1, 0), 8, 3)]
DTYPES = [F32, jnp.bfloat16]


def _grouped(sizes, d, p, pad, dtype, seed=0):
    M_ = sum(sizes) + pad
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    x = jax.random.normal(ks[0], (M_, d), F32).astype(dtype)
    w = jax.random.normal(ks[1], (len(sizes), d, p), F32).astype(dtype)
    ct = jax.random.normal(ks[2], (M_, p), F32).astype(dtype)
    return x, w, ct, jnp.asarray(sizes, jnp.int32)


def _dense(x, w, sizes):
    """Each row by its group's weight, rows past the groups zero."""
    gid = np.repeat(np.arange(len(sizes)), np.asarray(sizes))
    out = np.zeros((x.shape[0], w.shape[-1]), np.float32)
    xs, ws = np.asarray(x, np.float32), np.asarray(w, np.float32)
    for r, g in enumerate(gid):
        out[r] = xs[r] @ ws[g]
    return out


@pytest.mark.parametrize("sizes,d,p", GROUPS)
@pytest.mark.parametrize("dtype", DTYPES)
def test_grouped_matmul_and_its_gradients(sizes, d, p, dtype):
    x, w, ct, n = _grouped(sizes, d, p, 2, dtype)
    tol = dict(rtol=1e-5, atol=1e-5) if dtype == F32 else \
        dict(rtol=2e-2, atol=2e-2)
    np.testing.assert_allclose(np.asarray(M.grouped_matmul(x, w, n), F32),
                               _dense(x, w, sizes), **tol)
    f = lambda x, w: jnp.sum(M.grouped_matmul(x, w, n).astype(F32)
                             * ct.astype(F32))
    g = lambda x, w: jnp.sum(jnp.asarray(_dense_jnp(x, w, sizes))
                             * ct.astype(F32))
    for got, want in zip(jax.grad(f, (0, 1))(x, w),
                         jax.grad(g, (0, 1))(x, w)):
        np.testing.assert_allclose(np.asarray(got, F32),
                                   np.asarray(want, F32), **tol)


def _dense_jnp(x, w, sizes):
    gid = jnp.asarray(np.repeat(np.arange(len(sizes)), np.asarray(sizes)))
    rows = len(gid)
    y = jnp.einsum("md,mdp->mp", x[:rows].astype(F32),
                   w[gid].astype(F32))
    return jnp.concatenate([y, jnp.zeros((x.shape[0] - rows, w.shape[-1]))])


@pytest.mark.parametrize("sizes,d,p", GROUPS)
def test_grouped_matmul_under_vmap(sizes, d, p):
    """Per-sample gradients by vmap(grad), as the Opacus baseline makes
    them, against one sample at a time."""
    x, w, ct, n = _grouped(sizes, d, p, 1, F32)
    xs = jnp.stack([x, 2 * x, -x])
    f = lambda w, x: jnp.sum(M.grouped_matmul(x, w, n) * ct)
    got = jax.vmap(jax.grad(f), in_axes=(None, 0))(w, xs)
    want = jnp.stack([jax.grad(f)(w, xs[i]) for i in range(3)])
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


# ------------------------------------------------- grouped norms and sums
def _record_forms(B, H, d, p, T, seed):
    """One layer's rows in the three record forms models.moe makes, and the
    per-(expert, sample) gradients made one group at a time."""
    rng = np.random.RandomState(seed)
    gs = rng.randint(0, T + 1, size=(B, H)).astype(np.int32)
    Mr = int(gs.sum()) + 3
    tok = np.full(Mr, B * T, np.int32)
    tok[:gs.sum()] = rng.randint(0, B * T, size=gs.sum())
    w = np.zeros(Mr, np.float32)
    w[:gs.sum()] = rng.rand(gs.sum())
    xs = rng.randn(B * T, d).astype(np.float32)
    dy = rng.randn(B * T, p).astype(np.float32)
    a = np.concatenate([xs, np.zeros((1, d), np.float32)])[tok]
    ds = np.concatenate([dy, np.zeros((1, p), np.float32)])[tok] * w[:, None]
    grads, r = np.zeros((H, B, d, p)), 0
    for e in range(H):
        for b in range(B):
            grads[e, b] = a[r:r + gs[b, e]].T @ ds[r:r + gs[b, e]]
            r += gs[b, e]
    forms = {
        "rows": ({"a": a, "gs": gs}, ds),
        "gathered": ({"a": xs, "rows": tok, "gs": gs}, ds),
        "combined": ({"a": a, "out": tok, "w": w, "gs": gs},
                     dy.reshape(B, T, p)),
    }
    return forms, grads


@pytest.mark.parametrize("form", ["rows", "gathered", "combined"])
@pytest.mark.parametrize("B,H,d,p,T", [(2, 3, 5, 7, 4), (3, 2, 256, 9, 3)])
def test_grouped_norms_and_sums(monkeypatch, form, B, H, d, p, T):
    # a budget of 128 columns' worth cuts d = 256 into two chunks
    monkeypatch.setattr(ghost, "MOE_CHUNK_ELEMS", 128 * H * B * p)
    forms, grads = _record_forms(B, H, d, p, T, seed=d + p)
    rec, ds = forms[form]
    rec = {k: jnp.asarray(v) for k, v in rec.items()}
    C = jnp.asarray(np.linspace(0.5, 1.5, B), F32)
    want_n = np.sum(grads ** 2, axis=(0, 2, 3))
    want_g = np.einsum("ebdp,b->edp", grads, np.asarray(C))
    np.testing.assert_allclose(ghost.sq_norm_moe(rec, jnp.asarray(ds)),
                               want_n, rtol=1e-4)
    np.testing.assert_allclose(
        ghost.weighted_grad_moe(rec, C, jnp.asarray(ds), F32), want_g,
        rtol=1e-4, atol=1e-4)
    # stacked over two layers: the norms add, the sums stack
    two = {k: jnp.stack([v, v]) for k, v in rec.items()}
    ds2 = jnp.stack([jnp.asarray(ds)] * 2)
    np.testing.assert_allclose(ghost.sq_norm_moe(two, ds2), 2 * want_n,
                               rtol=1e-4)
    assert ghost.weighted_grad_moe(two, C, ds2).shape == (2, H, d, p)


# ------------------------------------------------------------- BK, policy
def _model(B, T):
    cfg = _cfg(n_experts=16, experts_held=8, first_expert=4)
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    params["blocks"]["mlp"]["router"]["w"] = \
        params["blocks"]["mlp"]["router"]["w"] * 4.0
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, T), 0, cfg.vocab)
    return model, params, {"tokens": tokens}


def _per_row(model, params, batch, policy):
    """Per-row jax.grad -> per-group norms -> clip -> sum."""
    res = resolve_policy(policy, flatten(params))
    gfn = jax.grad(lambda p, row: model.apply(p, {"tokens": row[None]},
                                              Tape(None))[0])
    rows = batch["tokens"]
    per = [flatten(gfn(params, rows[i])) for i in range(rows.shape[0])]
    norms, sums = {}, {}
    for unit in res.units:
        sq = jnp.stack([sum(jnp.sum(jnp.square(g[p])) for p in unit.paths)
                        for g in per])
        norms[unit.name] = jnp.sqrt(sq)
        C = unit.clip_fn()(norms[unit.name])
        for p in unit.paths:
            sums[p] = sum(C[i] * g[p] for i, g in enumerate(per))
    return norms, sums


@pytest.mark.parametrize("mode", ["bk", "bk-mixghost", "bk-mixopt"])
@pytest.mark.parametrize("B,T", [(3, 12), (2, 20)])
def test_bk_under_the_three_group_policy(mode, B, T):
    model, params, batch = _model(B, T)
    policy = get_policy("deepseek-moe-16b", mode=mode, sigma=0.0)
    with jax.default_matmul_precision("highest"):
        sums, aux = jax.jit(lambda p, b: bk_clipped_sum(
            model.apply, p, b, policy))(params, batch)
        norms, want = _per_row(model, params, batch, policy)
    for name in ("experts", "router", "dense"):
        np.testing.assert_allclose(aux["group_norms"][name], norms[name],
                                   rtol=1e-4)
    for p in want:
        np.testing.assert_allclose(sums[p], want[p], rtol=2e-4, atol=1e-7,
                                   err_msg=p)


def test_scopes_reach_the_compiled_step():
    model, params, batch = _model(2, 8)
    fn = make_grad_fn(model.apply, get_policy("deepseek-moe-16b", sigma=1.0))
    text = jax.jit(fn).lower(params, batch,
                             jax.random.PRNGKey(0)).compile().as_text()
    names = set(re.findall(r'op_name="([^"]*)"', text))
    segs = {s for n in names for s in n.split("/")}
    assert {"moe_dispatch", "moe_experts"} <= segs
    for tap in ("up", "down"):
        key = f"blocks.mlp.experts.{tap}#moe.s"
        assert any(f"/bk_norms/{key}/" in n for n in names), key
        assert any(f"/bk_clipped_sum/{key}/" in n for n in names), key


@pytest.mark.parametrize("held,first", [(4, 14), (0, 1), (20, 0)])
def test_a_share_outside_the_router_is_refused(held, first):
    with pytest.raises(ValueError):
        M.held(_cfg(experts_held=held, first_expert=first))


def test_dp_step_runs_with_noise():
    model, params, batch = _model(2, 8)
    fn = jax.jit(make_grad_fn(model.apply, DPConfig(mode="bk-mixopt",
                                                    sigma=0.5)))
    grads, aux = fn(params, batch, jax.random.PRNGKey(3))
    for p, g in flatten(grads).items():
        assert np.all(np.isfinite(np.asarray(g))), p
