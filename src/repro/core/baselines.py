"""Baseline DP implementations the paper compares against (Table 2).

Every baseline computes the SAME private gradient as BK (same math, different
time/space tradeoff) — tests assert exact agreement — and every baseline
honors the full PrivacyPolicy semantics (per-group clipping units, frozen
groups, pluggable noise), so policy tests can use them as references:

  non-private   1 bwd, no clipping                            (reference point)
  TF-Privacy    B sequential backprops (lax.map)              6BTpd, slow
  Opacus        vmap per-sample grads, instantiated           8BTpd, Bpd memory
  FastGradClip  per-sample norms then 2nd bwd of reweighted   8BTpd
  GhostClip     ghost norms (taps) then 2nd full bwd          10BTpd + 2BT^2(p+d)

Group-wise clipping gives each clip unit its own factor C_i^(u), so the
"reweighted loss" trick of FastGradClip/GhostClip (one backward of
sum_i C_i L_i) generalizes to one VJP of the per-sample loss VECTOR per unit
with cotangent C^(u) — still no per-sample weight gradients.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.bk import (batch_size_of, record_sq_norm, split_param_paths,
                           tap_structs)
from repro.core.policy import (as_policy, finalize_noise, norm_aux,
                               resolve_policy, unit_clip_factors)
from repro.core.tape import Tape, parse_key
from repro.utils.tree import flatten, unflatten

F32 = jnp.float32


def _loss_all(apply_fn, params, batch):
    return apply_fn(params, batch, Tape(None))  # (B,) per-sample losses


def _single(apply_fn, params, sample):
    batch1 = jax.tree_util.tree_map(lambda x: x[None], sample)
    return _loss_all(apply_fn, params, batch1)[0]


def _unit_sq_norms(flat_grads, res, B, leading_batch: bool):
    """Per-clip-unit per-sample (or scalar) squared norms from a flat grad
    dict; frozen leaves are excluded."""
    shape = (B,) if leading_batch else ()
    sq = [jnp.zeros(shape, F32) for _ in res.units]
    for p, g in flat_grads.items():
        if p in res.frozen:
            continue
        g = g.astype(F32)
        axes = tuple(range(1, g.ndim)) if leading_batch else None
        u = res.unit_of[p]
        sq[u] = sq[u] + jnp.sum(g * g, axis=axes)
    return sq


def _clip_sum_noise(per_sample_grads, losses, rng, policy, params, B, step,
                    mesh=None, pspecs=None):
    """Shared tail: per-unit norms -> C^(u) -> weighted sum -> noise.
    per_sample_grads has leading B on every leaf."""
    res = resolve_policy(policy, flatten(params))
    flat = flatten(per_sample_grads)
    sq = _unit_sq_norms(flat, res, B, leading_batch=True)
    unit_norms, unit_C = unit_clip_factors(res, sq)
    summed = {}
    for p, g in flat.items():
        if p in res.frozen:
            summed[p] = jnp.zeros(g.shape[1:], g.dtype)
        else:
            summed[p] = jnp.einsum("b...,b->...", g.astype(F32),
                                   unit_C[res.unit_of[p]]).astype(g.dtype)
    summed = finalize_noise(policy, res, summed, rng, float(B), step,
                            mesh=mesh, pspecs=pspecs)
    return unflatten(summed), norm_aux(res, losses, sq, unit_norms, unit_C)


def _unit_weighted_grads(apply_fn, params, batch, res, unit_C):
    """sum_i C_i^(u(p)) g_i[p] for every param, WITHOUT per-sample grads:
    one VJP of the per-sample loss vector per clip unit (cotangent C^(u)),
    then select each unit's own leaves. Frozen leaves come back zero."""
    losses, vjp_fn = jax.vjp(lambda p: _loss_all(apply_fn, p, batch), params)
    flat_params = flatten(params)
    flat_out = {p: jnp.zeros_like(v) for p, v in flat_params.items()}
    for u, (unit, C) in enumerate(zip(res.units, unit_C)):
        (g_u,) = vjp_fn(jax.lax.stop_gradient(C).astype(losses.dtype))
        fg = flatten(g_u)
        for p in unit.paths:
            flat_out[p] = fg[p]
    return losses, flat_out


# ----------------------------------------------------------------- baselines
def nonprivate_grad(apply_fn, params, batch, rng, cfg, step=None,
                    mesh=None, pspecs=None):
    """The plain mean gradient, its forward and backward in the
    ``jax.named_scope`` ``grad``."""
    policy = as_policy(cfg)
    res = resolve_policy(policy, flatten(params))

    def mean_loss(p):
        return jnp.mean(_loss_all(apply_fn, p, batch))

    with jax.named_scope("grad"):
        loss, grads = jax.value_and_grad(mean_loss)(params)
    if res.frozen:  # policies freeze groups even without clipping/noise
        flat = flatten(grads)
        for p in res.frozen:
            flat[p] = jnp.zeros_like(flat[p])
        grads = unflatten(flat)
    return grads, {"loss": loss}


def opacus_grad(apply_fn, params, batch, rng, cfg, step=None,
                mesh=None, pspecs=None):
    """vmap(grad) — instantiates all B per-sample gradients (module 4)."""
    policy = as_policy(cfg)
    B = batch_size_of(batch)
    gfn = jax.grad(lambda p, s: _single(apply_fn, p, s))
    per_g = jax.vmap(gfn, in_axes=(None, 0))(params, batch)
    losses = _loss_all(apply_fn, params, batch)
    return _clip_sum_noise(per_g, losses, rng, policy, params, B, step,
                           mesh=mesh, pspecs=pspecs)


def tfprivacy_grad(apply_fn, params, batch, rng, cfg, step=None,
                   mesh=None, pspecs=None):
    """B sequential backprops via lax.map (memory-light, slow)."""
    policy = as_policy(cfg)
    B = batch_size_of(batch)
    vg = jax.value_and_grad(lambda p, s: _single(apply_fn, p, s), argnums=0)
    losses, per_g = jax.lax.map(lambda s: vg(params, s), batch)
    return _clip_sum_noise(per_g, losses, rng, policy, params, B, step,
                           mesh=mesh, pspecs=pspecs)


def fastgradclip_grad(apply_fn, params, batch, rng, cfg, step=None,
                      mesh=None, pspecs=None):
    """Lee & Kifer 2020: per-sample norms (grads discarded), then a second
    backprop of the reweighted loss — one VJP per clip unit."""
    policy = as_policy(cfg)
    B = batch_size_of(batch)
    res = resolve_policy(policy, flatten(params))
    gfn = jax.grad(lambda p, s: _single(apply_fn, p, s))
    sq_rows = jax.lax.map(
        lambda s: jnp.stack(_unit_sq_norms(flatten(gfn(params, s)), res, B,
                                           leading_batch=False)), batch)
    sq = [sq_rows[:, u] for u in range(len(res.units))]
    unit_norms, unit_C = unit_clip_factors(res, sq)

    losses, flat = _unit_weighted_grads(apply_fn, params, batch, res, unit_C)
    flat = finalize_noise(policy, res, flat, rng, float(B), step,
                          mesh=mesh, pspecs=pspecs)
    return unflatten(flat), norm_aux(res, losses, sq, unit_norms, unit_C)


def ghostclip_grad(apply_fn, params, batch, rng, cfg, step=None,
                   mesh=None, pspecs=None):
    """Li et al. 2021 / Bu et al. 2022a: ghost norms from a tapped first
    backprop (no per-sample grads), then a second full backprop per unit."""
    policy = as_policy(cfg)
    B = batch_size_of(batch)
    flat_params = flatten(params)
    tap_struct = tap_structs(apply_fn, params, batch)
    _, psp_paths = split_param_paths(params, tap_struct)
    res = resolve_policy(policy, flat_params)
    active_taps = sorted(k for k in tap_struct
                         if parse_key(k)[0] + "/w" not in res.frozen)
    psp_active = [p for p in psp_paths if p not in res.frozen]
    taps0 = {k: jnp.zeros(tap_struct[k].shape, tap_struct[k].dtype)
             for k in active_taps}
    psp0 = {p: jnp.broadcast_to(flat_params[p], (B,) + flat_params[p].shape)
            for p in psp_active}

    def run(taps, psp):
        merged = dict(flat_params)
        merged.update(psp)
        tape = Tape(taps)
        losses = apply_fn(unflatten(merged), batch, tape)
        return jnp.sum(losses), tape.acts

    _, vjp_fn, acts = jax.vjp(run, taps0, psp0, has_aux=True)
    ds_taps, g_psp = vjp_fn(jnp.asarray(1.0, F32))

    sq = [jnp.zeros((B,), F32) for _ in res.units]
    for key in active_taps:
        wpath = parse_key(key)[0] + "/w"
        nk, _ = record_sq_norm(key, acts[key], ds_taps[key], "bk",
                               policy.use_kernels, res.method_for(wpath))
        u = res.unit_of[wpath]
        sq[u] = sq[u] + nk
    for p in psp_active:
        g = g_psp[p].astype(F32)
        u = res.unit_of[p]
        sq[u] = sq[u] + jnp.sum(g * g, axis=tuple(range(1, g.ndim)))
    unit_norms, unit_C = unit_clip_factors(res, sq)

    losses, flat = _unit_weighted_grads(apply_fn, params, batch, res, unit_C)
    flat = finalize_noise(policy, res, flat, rng, float(B), step,
                          mesh=mesh, pspecs=pspecs)
    return unflatten(flat), norm_aux(res, losses, sq, unit_norms, unit_C)
