"""The Book-Keeping (BK) engine — Algorithm 1 of the paper, JAX-native.

One jax.vjp w.r.t. (taps, per-sample params) yields, in a SINGLE
back-propagation and without ever instantiating per-sample weight gradients:

  * every layer's output gradient dL/ds_(l)      (tap cotangents — book-keeping)
  * per-sample gradients of vector params (B,..) (psp cotangents — the 0.1%)

and because the weights themselves are not differentiated, XLA never emits
the non-private parameter-gradient matmuls (ghost differentiation).

Phases (all inside one jit-able pure function):
  1. fwd + output-grad bwd via vjp            — modules 1 + 2a
  2. per-sample squared norms per tapped op   — module 3 (ghost) or 4 (direct)
     + vector-param norms; aggregate across layers; clip factors C_i
  3. weighted gradients G_l = a^T diag(C) ds  — module 2b'/5
  4. Gaussian noise, scale by 1/B

Phases 1-3 run inside the ``jax.named_scope`` ``bk_taps``, ``bk_norms`` and
``bk_clipped_sum``, and each tap's work in phases 2-3 inside one named by
its key (``tap_scope``): compile-time names that reach the profiler's op
paths and add no ops.

Modes:
  'bk'           ghost norm everywhere (base BK)
  'bk-mixghost'  layerwise ghost-vs-direct for the *norm* only
  'bk-mixopt'    layerwise for norm AND weighted grad (reuses instantiated
                 per-sample grads for module 5 when direct is chosen)

Mesh lowering: every entry point takes an optional ``mesh``. Under a mesh
whose batch axes divide B, the per-sample record compute stays batch-sharded
end to end — fused kernels run inside a shard_map on their local batch shard
(per-sample norms reduce at size B_local and STAY sharded; each weighted
gradient pays exactly one psum over the batch axes), the jnp paths get
sharding constraints so GSPMD keeps the same layout, and phase-4 noise is
generated shard-local (see core.noise.sharded_normal).
"""
from __future__ import annotations

from dataclasses import dataclass, replace
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import ghost
from repro.core.clipping import get_clip_fn
from repro.core.policy import (as_policy, finalize_noise, norm_aux,
                               resolve_policy, unit_clip_factors)
from repro.core.tape import Tape, load_record, parse_key, store_record
from repro.utils.tree import flatten, unflatten

F32 = jnp.float32

BK_MODES = ("bk", "bk-mixghost", "bk-mixopt")


# ----------------------------------------------------------- mesh lowering
def mesh_batch_axes(mesh) -> tuple:
    """Mesh axes the batch dim shards over (mirrors launch.mesh.batch_axes;
    duplicated here so core never imports launch)."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def batch_shard(mesh, B: int):
    """-> (batch_axes, n_shards) when ``mesh`` can split B, else None."""
    if mesh is None:
        return None
    ba = mesh_batch_axes(mesh)
    n = 1
    for a in ba:
        n *= mesh.shape[a]
    if n <= 1 or B % n:
        return None
    return ba, n


def _bspec(ndim: int, bdim: int, ba) -> P:
    return P(*(ba if i == bdim else None for i in range(ndim)))


def _constrain(x, mesh, spec):
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def _shard_call(mesh, fn, args, in_specs, out_specs, psum_axes=None):
    """Run a per-sample kernel batch-sharded: each device computes its local
    batch slice; ``psum_axes`` reduces sum-typed outputs (weighted grads)
    once across the batch axes — the single cross-device reduction per clip
    unit the mesh-lowered step pays."""
    body = fn
    if psum_axes:
        body = lambda *a: jax.lax.psum(fn(*a), psum_axes)
    return jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)(*args)


def _local(shape, bdim: int, n: int) -> tuple:
    return tuple(s // n if i == bdim else s for i, s in enumerate(shape))


@dataclass(frozen=True)
class DPConfig:
    clipping: str = "automatic"      # clipping fn name (core.clipping)
    R: float = 1.0                   # clipping threshold / normalizer
    sigma: float = 0.0               # noise multiplier (0 = clipping only)
    mode: str = "bk"                 # implementation (BK_MODES + baselines)
    use_kernels: bool = True         # fused Pallas kernels via kernels.dispatch
    gamma: float = 0.01              # automatic-clipping stability constant
    tape_policy: str = "native"      # tap-record residency between phases 2-3
                                     # (core.tape.TAPE_POLICIES: native | bf16
                                     # | int8 | recompute | auto)
    tape_chunks: int = 1             # phase-3 re-derivation chunks (recompute)

    def clip_fn(self) -> Callable:
        kw = {"gamma": self.gamma} if self.clipping == "automatic" else {}
        return get_clip_fn(self.clipping, self.R, **kw)


# --------------------------------------------------------------------- utils
def batch_size_of(batch: dict) -> int:
    return jax.tree_util.tree_leaves(batch)[0].shape[0]


def tap_structs(apply_fn, params, batch):
    """Tap zero-structure via one (free) eval_shape pass."""
    return tap_act_structs(apply_fn, params, batch)[0]


def tap_act_structs(apply_fn, params, batch):
    """-> (tap zero structure, activation-record structure), one free
    eval_shape pass (the residency planner needs both shapes)."""

    def shape_run(p, b):
        tape = Tape(None)
        apply_fn(p, b, tape)
        return tape.tap_zeros, tape.acts

    return jax.eval_shape(shape_run, params, batch)


def _tap_w(key: str) -> str:
    return parse_key(key)[0] + "/w"


def tap_scope(key: str):
    """The profiler scope of one tap's norm or weighted-gradient work: the
    tap key with '/' replaced by '.', so that it stays one segment of the
    op path."""
    return jax.named_scope(key.replace("/", "."))


def split_param_paths(params, tap_struct):
    """-> (ghost_w_paths, psp_paths). Ghost leaves are '<tap path>/w'."""
    flat = flatten(params)
    tapped = {parse_key(k)[0] + "/w" for k in tap_struct}
    ghost_paths = sorted(p for p in flat if p in tapped)
    psp_paths = sorted(p for p in flat if p not in tapped)
    missing = tapped - set(flat)
    if missing:
        raise ValueError(f"tapped ops without matching '<path>/w' param: {sorted(missing)}")
    dead = [p for p in psp_paths if p.endswith("/w")]
    if dead:
        raise ValueError(
            "untapped weight params (dead or mis-named tap — every '/w' leaf "
            f"must belong to a tapped generalized-linear op): {dead}")
    return ghost_paths, psp_paths


# ------------------------------------------------------------- norm dispatch
def record_sq_norm(key: str, act, ds, mode: str, use_kernels: bool,
                   method: str = "", mesh=None, shard=None,
                   allow_cache: bool = True):
    """Per-sample squared norm for one tapped op.

    Every kind routes through kernels.dispatch: the plan fixes ghost-vs-direct
    (the paper's layerwise rule; mode 'bk' forces ghost; a ParamGroup's
    ``method`` override wins over both) and, when ``use_kernels``, whether the
    fused Pallas kernel or the jnp einsum runs plus its block sizes. Returns
    (sq_norms (B,), cached) where cached optionally carries the instantiated
    per-sample grads for mixopt reuse in phase 3. ``allow_cache=False``
    suppresses that instantiation — mixopt's cache is itself a residency
    decision, and a non-native tape policy overrides it (the streamed
    engine then holds the compressed cotangent, or nothing, instead).

    With ``shard`` = (batch_axes, n) the kernel runs inside a shard_map on
    its local batch slice (the plan is fitted to the LOCAL shapes, matching
    what each device executes) and the (B,) norms come back batch-sharded;
    jnp paths are left to GSPMD.
    """
    from repro.kernels import dispatch
    _, kind, _ = parse_key(key)
    ba, n = shard if shard else ((), 1)
    if kind == "mm":
        bdim = act.ndim - 3
        a_shape = _local(act.shape, bdim, n)
        ds_shape = _local(ds.shape, bdim, n)
        plan = dispatch.norm_plan("mm", a_shape, ds_shape, mode, method)
        fused = use_kernels and plan.impl == "kernel"
        if plan.method == "ghost":
            if fused:
                from repro.kernels import ops as kops
                fn = lambda a, d: kops.ghost_norm_mm(a, d, **plan.kwargs())
                if n > 1:
                    return _shard_call(
                        mesh, fn, (act, ds),
                        (_bspec(act.ndim, bdim, ba),
                         _bspec(ds.ndim, bdim, ba)), P(ba)), None
                return fn(act, ds), None
            return ghost.sq_norm_mm_ghost(act, ds), None
        B, d, p = act.shape[-3], act.shape[-1], ds.shape[-1]
        L = act.shape[0] if act.ndim == 4 else 1
        # the cache lives batch-sharded: its footprint (and the decision to
        # keep it) is per-device, like the kernel plans above
        small = L * (B // n) * d * p <= ghost.MAP_THRESHOLD
        if mode == "bk-mixopt" and small and allow_cache:
            # mixopt's defining move (paper Sec 3.3): instantiate once, reuse
            # for module 5 in phase 3. Takes precedence over the fused kernel
            # — the kernel saves the per-sample-grad space, but mixopt chose
            # direct *because* it is willing to spend that space to halve the
            # phase-3 FLOPs; only cache when cheap to keep (else re-einsum)
            eq = "lbtd,lbtp->lbdp" if act.ndim == 4 else "btd,btp->bdp"
            g = jnp.einsum(eq, act.astype(F32), ds.astype(F32))
            axes = tuple(i for i in range(g.ndim) if i != (1 if g.ndim == 4 else 0))
            return jnp.sum(g * g, axis=axes), g
        if fused:
            from repro.kernels import ops as kops
            fn = lambda a, d: kops.direct_norm_mm(a, d, **plan.kwargs())
            if n > 1:
                return _shard_call(
                    mesh, fn, (act, ds),
                    (_bspec(act.ndim, bdim, ba),
                     _bspec(ds.ndim, bdim, ba)), P(ba)), None
            return fn(act, ds), None
        return ghost.sq_norm_mm_direct(act, ds), None
    if kind == "emb":
        bdim = act.ndim - 2
        plan = dispatch.norm_plan("emb", _local(act.shape, bdim, n),
                                  _local(ds.shape, bdim, n), mode, method)
        if use_kernels and plan.impl == "kernel":
            from repro.kernels import ops as kops
            fn = lambda i, d: kops.ghost_norm_emb(i, d, **plan.kwargs())
            if n > 1:
                return _shard_call(
                    mesh, fn, (act, ds),
                    (_bspec(act.ndim, bdim, ba),
                     _bspec(ds.ndim, bdim, ba)), P(ba)), None
            return fn(act, ds), None
        return ghost.sq_norm_emb(act, ds), None
    if kind == "moe":
        a = act["a"]
        bdim = a.ndim - 4
        plan = dispatch.norm_plan("moe", _local(a.shape, bdim, n),
                                  _local(ds.shape, bdim, n), mode, method)
        fused = use_kernels and plan.impl == "kernel"
        rec_specs = {"a": _bspec(a.ndim, bdim, ba),
                     "mask": _bspec(act["mask"].ndim, bdim, ba)} if n > 1 \
            else None
        if plan.method == "ghost":
            if fused:
                from repro.kernels import ops as kops
                if n > 1:
                    return _shard_call(
                        mesh, kops.ghost_norm_moe, (act, ds),
                        (rec_specs, _bspec(ds.ndim, bdim, ba)), P(ba)), None
                return kops.ghost_norm_moe(act, ds), None
            return ghost.sq_norm_moe_ghost(act, ds), None
        if fused:
            from repro.kernels import ops as kops
            fn = lambda r, d: kops.direct_norm_moe(r, d, **plan.kwargs())
            if n > 1:
                return _shard_call(
                    mesh, fn, (act, ds),
                    (rec_specs, _bspec(ds.ndim, bdim, ba)), P(ba)), None
            return fn(act, ds), None
        return ghost.sq_norm_moe_direct(act, ds), None
    raise ValueError(f"unknown tap kind in key {key!r}")


def record_weighted_grad(key: str, act, ds, C, cached, use_kernels: bool,
                         out_dtype, vocab: int = 0, mesh=None, shard=None):
    """Phase-3 weighted gradient G = a^T diag(C) ds for one tap. Under
    ``shard`` each device contracts its local batch slice and the partial
    sums meet in ONE psum over the batch axes — the only cross-device
    reduction the clipped sum pays."""
    from repro.kernels import dispatch
    _, kind, _ = parse_key(key)
    ba, n = shard if shard else ((), 1)
    if kind == "mm":
        if cached is not None:  # mixopt module-5 reuse: sum_i C_i g_i (2Bpd)
            eq = "lbdp,b->ldp" if cached.ndim == 4 else "bdp,b->dp"
            return jnp.einsum(eq, cached, C.astype(F32)).astype(out_dtype)
        if use_kernels:
            bdim = act.ndim - 3
            plan = dispatch.grad_plan("mm", _local(act.shape, bdim, n),
                                      _local(ds.shape, bdim, n))
            if plan.impl == "kernel":
                from repro.kernels import ops as kops
                fn = lambda a, c, d: kops.clipped_grad_mm(a, c, d,
                                                          **plan.kwargs())
                if n > 1:
                    return _shard_call(
                        mesh, fn, (act, C, ds),
                        (_bspec(act.ndim, bdim, ba), P(ba),
                         _bspec(ds.ndim, bdim, ba)), P(),
                        psum_axes=ba).astype(out_dtype)
                return fn(act, C, ds).astype(out_dtype)
        return ghost.weighted_grad_mm(act, C, ds, out_dtype)
    if kind == "emb":
        if use_kernels:
            bdim = act.ndim - 2
            plan = dispatch.grad_plan("emb", _local(act.shape, bdim, n),
                                      _local(ds.shape, bdim, n), vocab)
            if plan.impl == "kernel":
                from repro.kernels import ops as kops
                fn = lambda i, c, d: kops.clipped_grad_emb(i, c, d, vocab,
                                                           **plan.kwargs())
                if n > 1:
                    return _shard_call(
                        mesh, fn, (act, C, ds),
                        (_bspec(act.ndim, bdim, ba), P(ba),
                         _bspec(ds.ndim, bdim, ba)), P(),
                        psum_axes=ba).astype(out_dtype)
                return fn(act, C, ds).astype(out_dtype)
        return ghost.weighted_grad_emb(act, C, ds, vocab, out_dtype)
    if kind == "moe":
        if use_kernels:
            a = act["a"]
            bdim = a.ndim - 4
            plan = dispatch.grad_plan("moe", _local(a.shape, bdim, n),
                                      _local(ds.shape, bdim, n))
            if plan.impl == "kernel":
                from repro.kernels import ops as kops
                fn = lambda r, c, d: kops.clipped_grad_moe(r, c, d,
                                                           **plan.kwargs())
                if n > 1:
                    rec_specs = {"a": _bspec(a.ndim, bdim, ba),
                                 "mask": _bspec(act["mask"].ndim, bdim, ba)}
                    return _shard_call(
                        mesh, fn, (act, C, ds),
                        (rec_specs, P(ba), _bspec(ds.ndim, bdim, ba)), P(),
                        psum_axes=ba).astype(out_dtype)
                return fn(act, C, ds).astype(out_dtype)
        return ghost.weighted_grad_moe(act, C, ds, out_dtype)
    raise ValueError(f"unknown tap kind in key {key!r}")


def plan_report(apply_fn, params, batch, cfg) -> dict:
    """Resolved kernel-dispatch plans per tap, from one free eval_shape pass.

    -> {tap_key: {'norm': Plan, 'grad': Plan, 'tape': TapePlan}} —
    observability for the engine/benchmarks; no compute. Policy-aware:
    frozen-group taps are absent from the report (they emit no norm/grad
    work at all), per-group method overrides show up in the norm plan, and
    the 'tape' entry is the tap's resolved residency decision (group
    ``tape`` override / policy ``tape_policy`` / planner 'auto') with its
    held-bytes and re-derivation-FLOPs cost numbers."""
    from repro.kernels import dispatch
    policy = as_policy(cfg)

    taps, acts = tap_act_structs(apply_fn, params, batch)
    flat_params = flatten(params)
    res = resolve_policy(policy, flat_params)
    active = sorted(k for k in taps if _tap_w(k) not in res.frozen)
    tape_pol = resolve_tape(policy, res, {k: taps[k] for k in active}, acts)
    stream_keys = _streamed_taps(res, active)
    report = {}
    for key in sorted(acts):
        path, kind, _ = parse_key(key)
        wpath = path + "/w"
        if wpath in res.frozen:
            continue
        a_shape = acts[key]["a"].shape if kind == "moe" else acts[key].shape
        vocab = flat_params[wpath].shape[-2] if kind == "emb" else 0
        plans = {
            "norm": dispatch.norm_plan(kind, a_shape, taps[key].shape,
                                       policy.mode, res.method_for(wpath)),
            "grad": dispatch.grad_plan(kind, a_shape, taps[key].shape, vocab),
        }
        if key in stream_keys:
            # streamed single-tap unit: phases 2+3 fuse at this tap — the
            # 'fused' plan says HOW (one kernel launch vs composed split)
            # and the 'stream' tape entry records that nothing is held
            plans["fused"] = dispatch.fused_plan(kind, a_shape,
                                                 taps[key].shape, policy.mode,
                                                 res.method_for(wpath))
        if not policy.use_kernels:  # report what will actually run
            plans = {k: replace(p, impl="jnp") for k, p in plans.items()}
        plans["tape"] = dispatch.tape_plan(
            kind, a_shape, taps[key].shape,
            "stream" if key in stream_keys else tape_pol[key],
            itemsize=taps[key].dtype.itemsize)
        report[key] = plans
    return report


# --------------------------------------------------------- tape residency
def pad_batch(batch, mesh, B: int):
    """-> (batch, mask | None, B_padded).

    Pads the batch to the next multiple of the mesh's batch-shard count so
    the shard_map'd kernel path engages on non-divisible batches (instead of
    silently falling back to GSPMD over the jnp einsums). ``mask`` (B_pad,)
    f32 marks real samples; it folds into the per-sample loss SUM (zeroing
    every pad cotangent at the source) and into the clip factors (belt and
    braces — pad cotangents are exact zeros already).

    Pad rows REPEAT the last real sample via a gather rather than appending
    zeros with a concatenate: the SPMD partitioner mis-lowers an in-graph
    concat whose operand does not divide the batch axes (observed: real
    rows turn NaN once the per-sample-param constraint forces data
    sharding), and repeated real rows are also numerically safe for models
    whose loss degenerates on all-zero samples."""
    if mesh is None:
        return batch, None, B
    ba = mesh_batch_axes(mesh)
    n = 1
    for a in ba:
        n *= mesh.shape[a]
    if n <= 1 or B % n == 0:
        return batch, None, B
    B_pad = -(-B // n) * n
    idx = jnp.minimum(jnp.arange(B_pad), B - 1)
    batch = jax.tree_util.tree_map(lambda x: jnp.take(x, idx, axis=0), batch)
    mask = (jnp.arange(B_pad) < B).astype(F32)
    return batch, mask, B_pad


def resolve_tape(policy, res, tap_struct, act_struct) -> dict:
    """Per-active-tap storage decision: the ``REPRO_TAPE`` force env wins
    outright (the same knob the planner/report honor — the engine must
    agree with what kernel_report claims), then the ParamGroup ``tape``
    override, then the policy-level ``tape_policy``, with 'auto' resolved
    by the dispatch residency planner (kernels.dispatch.tape_plan)."""
    import os

    from repro.kernels import dispatch
    force = os.environ.get("REPRO_TAPE", "")
    out = {}
    for key in sorted(tap_struct):
        wpath = _tap_w(key)
        if wpath in res.frozen:
            continue
        pol = force or res.group_of[wpath].tape or policy.tape_policy
        if pol == "auto":
            _, kind, _ = parse_key(key)
            a = (act_struct[key]["a"].shape if kind == "moe"
                 else act_struct[key].shape)
            pol = dispatch.tape_plan(
                kind, a, tap_struct[key].shape,
                itemsize=tap_struct[key].dtype.itemsize).store
        out[key] = pol
    return out


def _act_dtype(struct):
    return struct["a"].dtype if isinstance(struct, dict) else struct.dtype


def _streamed_taps(res, active_taps) -> frozenset:
    """Taps whose clip unit STREAMS: the unit's norm closes over exactly this
    one tap's cotangent (single-path layer-scope units), so phases 2+3 fuse
    at the tap — norm, clip factor and weighted grad are emitted the moment
    the cotangent is produced, and nothing is book-kept between phases.

    Restricted to scope='layer' groups by design: a flat/group-scope unit
    that happens to own a single tap keeps the two-phase flow so existing
    scopes stay bitwise-identical (streaming ignores the tap's residency
    override — there is nothing to hold — which would silently change what
    a bf16/int8 ``tape`` request computes). ``REPRO_STREAM=0`` is the kill
    switch (forces two-phase everywhere; parity tests diff against it)."""
    import os
    if os.environ.get("REPRO_STREAM", "1") == "0":
        return frozenset()
    out = set()
    for key in active_taps:
        wpath = _tap_w(key)
        u = res.unit_of[wpath]
        if res.group_of[wpath].scope == "layer" \
                and res.units[u].paths == (wpath,):
            out.add(key)
    return frozenset(out)


# ------------------------------------------------------------------- BK core
def bk_clipped_sum(apply_fn, params, batch, cfg, mesh=None, rng=None):
    """Phases 1-3 of BK: the pre-noise clipped gradient SUM (flat dict),
    with managed tape residency.

    ``cfg`` is a DPConfig or PrivacyPolicy; each clipping unit of the
    resolved policy gets its own per-sample norm accumulator and clip factor
    C_i^(u), frozen-group taps/params are skipped outright (no cotangent is
    even requested — XLA never builds their book-keeping), and their grads
    come back as zeros.

    The backward is STREAMED, not hoarded: phase 1 linearizes the forward
    once and runs ONE transposed sweep for the cotangents; each tap's
    cotangent is consumed by its phase-2 norm as it is produced and then
    HELD per the tap's residency policy (``tape_policy`` / per-group
    ``tape``) — native (today's bitwise path), bf16/int8 compressed
    (runtime.compression stochastic rounding; norms stay fp32), or not at
    all ('recompute': NOTHING survives phase 2 for the tap — phase 3
    re-derives its weighted gradient with a reweighted-loss backward,
    one fresh forward + backward per chunk, rematting at the models' own
    jax.checkpoint scan-block boundaries; the extra forward is the
    ghost-clipping cost, see the phase-3 comment for why a residual-
    reusing transpose is worse). Each recompute chunk's backward is seeded
    through an optimization barrier carrying the clip factors, so phase
    2's cotangents are dead before any re-derivation runs. ``rng`` keys
    int8 stochastic rounding (path-stable folds; a fixed key when
    omitted).

    This is the accumulation unit for the physical/logical batch split
    (paper footnote 2): sum over microbatches, then noise ONCE per logical
    batch. Returns (flat_sums, aux).

    Under ``mesh`` the whole per-sample pipeline stays batch-sharded:
    per-sample vector-param broadcasts, squared-norm accumulators, clip
    factors and losses all live at B_local per device; fused kernels run
    shard_map'd on their local slice, and each weighted gradient pays
    exactly one psum across the batch axes. Batches that do NOT divide the
    batch-shard count are padded with masked samples (``pad_batch``) so the
    kernel path still engages."""
    from repro.core.noise import _path_rng
    policy = as_policy(cfg)
    assert policy.mode in BK_MODES, policy.mode
    B_real = batch_size_of(batch)
    batch, mask, B = pad_batch(batch, mesh, B_real)
    shard = batch_shard(mesh, B)
    ba = shard[0] if shard else ()
    flat_params = flatten(params)
    tap_struct, act_struct = tap_act_structs(apply_fn, params, batch)
    _, psp_paths = split_param_paths(params, tap_struct)
    res = resolve_policy(policy, flat_params)

    active_taps = sorted(k for k in tap_struct if _tap_w(k) not in res.frozen)
    psp_active = [p for p in psp_paths if p not in res.frozen]
    tape_pol = resolve_tape(policy, res,
                            {k: tap_struct[k] for k in active_taps},
                            act_struct)
    stream_keys = _streamed_taps(res, active_taps)
    # the activation-tape side resolves PER TAP (REPRO_TAPE force > group
    # ``tape`` override > policy default): records happen inside scan bodies
    # where keys are scope-relative, so the resolver receives the MERGED key
    # (tape._SCOPE_PREFIX) and maps it to its owning group's store
    import os
    _force_tape = os.environ.get("REPRO_TAPE", "")

    def _act_store_for(full_key: str) -> str:
        g = res.group_of.get(_tap_w(full_key))
        pol = _force_tape or (g.tape if g is not None else "") \
            or policy.tape_policy
        # recompute/auto keep acts native — they ARE the standard tape
        return "native" if pol in ("recompute", "auto") else pol

    act_stores = {k: _act_store_for(k) for k in active_taps}
    srng = None
    if any(v == "int8" for v in act_stores.values()) \
            or any(p == "int8" for p in tape_pol.values()):
        srng = rng if rng is not None else jax.random.PRNGKey(0)
    taps0 = {k: jnp.zeros(tap_struct[k].shape, tap_struct[k].dtype)
             for k in active_taps}
    psp0 = {p: jnp.broadcast_to(flat_params[p], (B,) + flat_params[p].shape)
            for p in psp_active}
    if shard:
        # pin the per-sample broadcasts batch-sharded so the transpose's psp
        # cotangents (true per-sample grads, B x param size) never
        # materialize replicated
        psp0 = {p: _constrain(v, mesh, _bspec(v.ndim, 0, ba))
                for p, v in psp0.items()}

    # ---- phase 1: one forward, linearized once; ONE transposed sweep for
    # the cotangents (with every tap at 'native' this is exactly the
    # monolithic jax.vjp — bitwise). The activation tape is stored in its
    # residency representation AT RECORD TIME (tape.act_storage): inside the
    # models' scan bodies, so the stacked native ys never materialize.
    # 'recompute' keeps acts native — that IS the standard activation tape
    # the paper's memory claim is measured against.
    from repro.core.tape import act_storage
    act_rng = (_path_rng(srng, "acts")
               if any(v == "int8" for v in act_stores.values()) else None)

    def run(taps, psp):
        merged = dict(flat_params)
        merged.update(psp)
        tape = Tape(taps)
        with act_storage(_act_store_for, act_rng):
            losses = apply_fn(unflatten(merged), batch, tape)
        lsum = jnp.sum(losses * mask) if mask is not None else jnp.sum(losses)
        return lsum, (losses, tape.acts)

    with jax.named_scope("bk_taps"):
        loss_sum, jvp_fn, (losses, stored_acts) = jax.linearize(
            run, taps0, psp0, has_aux=True)
        transpose = jax.linear_transpose(lambda dt, dp: jvp_fn(dt, dp),
                                         taps0, psp0)
        ds_taps, g_psp = transpose(jnp.ones_like(loss_sum))

    # ---- phase 2: per-unit per-sample norms + clip factors; each cotangent
    # is consumed by its norm as produced, then held per its tape policy.
    # STREAMED taps (single-tap layer-scope units) never hold anything:
    # their unit's clip decision closes over this one cotangent, so the
    # norm, the clip factor AND the phase-3 weighted grad all fire here —
    # one fused kernel launch where the dispatch cost model says the
    # per-sample grad fits VMEM, the composed norm+grad paths otherwise —
    # and the record is dead the moment the grad is emitted. ----
    from repro.kernels import dispatch
    unit_of = lambda p: res.unit_of[p]
    held, cache, acts_l, flat_grads = {}, {}, {}, {}

    def tap_norm(key, sq):
        """Phase 2 at one tap: its squared norms added into ``sq``."""
        wpath = _tap_w(key)
        pol = tape_pol[key]
        # bf16 records feed the consumers AS STORED: every norm/grad path
        # (fused kernels and the jnp einsums alike) upcasts per block with
        # f32 accumulation, so a wholesale dequant would only materialize
        # f32 copies of the book-kept state it exists to shrink. int8 needs
        # the (elementwise, consumer-fused) dequant.
        act = (stored_acts[key] if act_stores[key] == "bf16"
               else load_record(stored_acts[key],
                                _act_dtype(act_struct[key])))
        if key in stream_keys:
            u = unit_of(wpath)
            unit = res.units[u]
            ds, w = ds_taps[key], flat_params[wpath]
            _, kind, _ = parse_key(key)
            wv = mask if mask is not None else jnp.ones((B,), F32)
            n_ = shard[1] if shard else 1
            fplan = None
            if policy.use_kernels and kind == "mm" \
                    and not isinstance(act, dict):
                bdim = act.ndim - 3
                fplan = dispatch.fused_plan(
                    "mm", _local(act.shape, bdim, n_),
                    _local(ds.shape, bdim, n_), policy.mode,
                    res.method_for(wpath))
            if fplan is not None and fplan.method == "fused" \
                    and fplan.impl == "kernel":
                from repro.kernels import ops as kops
                fused = lambda a, d, v: kops.fused_clip_grad_mm(
                    a, d, v, unit.clipping, unit.R, unit.gamma)
                if shard:
                    # NOT _shard_call: only the grad psums across the batch
                    # axes — the per-sample sq norms stay batch-sharded
                    bdim = act.ndim - 3
                    body = lambda a, d, v: (
                        (lambda g_s: (jax.lax.psum(g_s[0], ba), g_s[1]))
                        (fused(a, d, v)))
                    G, sqk = jax.shard_map(
                        body, mesh=mesh,
                        in_specs=(_bspec(act.ndim, bdim, ba),
                                  _bspec(ds.ndim, bdim, ba), P(ba)),
                        out_specs=(P(), P(ba)),
                        check_vma=False)(act, ds, wv)
                else:
                    G, sqk = fused(act, ds, wv)
                flat_grads[wpath] = G.astype(w.dtype)
                sq[u] = sq[u] + sqk
            else:
                # composed streaming: op-identical to the two-phase flow for
                # this unit (norm -> constrain -> sqrt -> clip -> mask ->
                # weighted grad), just with nothing held in between
                nk, cached = record_sq_norm(key, act, ds, policy.mode,
                                            policy.use_kernels,
                                            res.method_for(wpath), mesh=mesh,
                                            shard=shard, allow_cache=True)
                s = sq[u] + nk
                if shard:
                    s = _constrain(s, mesh, P(ba))
                sq[u] = s
                C_u = unit.clip_fn()(jnp.sqrt(s)).astype(F32)
                if mask is not None:
                    C_u = C_u * mask
                vocab = w.shape[-2] if kind == "emb" else 0
                flat_grads[wpath] = record_weighted_grad(
                    key, act, ds, C_u, cached, policy.use_kernels, w.dtype,
                    vocab, mesh=mesh, shard=shard)
            return
        acts_l[key] = act
        nk, cached = record_sq_norm(key, acts_l[key], ds_taps[key],
                                    policy.mode, policy.use_kernels,
                                    res.method_for(wpath), mesh=mesh,
                                    shard=shard,
                                    allow_cache=(pol == "native"))
        cache[key] = cached
        held[key] = (None if pol == "recompute" else
                     store_record(ds_taps[key], pol,
                                  _path_rng(srng, key + "/ds")
                                  if pol == "int8" else None))
        u = unit_of(wpath)
        sq[u] = sq[u] + nk

    with jax.named_scope("bk_norms"):
        sq = [jnp.zeros((B,), F32) for _ in res.units]
        for key in active_taps:
            with tap_scope(key):
                tap_norm(key, sq)
        for p in psp_active:
            g = g_psp[p].astype(F32)
            u = unit_of(p)
            sq[u] = sq[u] + jnp.sum(g * g, axis=tuple(range(1, g.ndim)))
        if shard:
            # the (B,) accumulators (and the clip factors derived from them)
            # reduce locally at size B_local and STAY sharded into phase 3
            sq = [_constrain(s, mesh, P(ba)) for s in sq]
        unit_norms, unit_C = unit_clip_factors(res, sq)
        if mask is not None:
            unit_C = [c * mask for c in unit_C]

    # ---- phase 3: weighted gradients ----------------------------------------
    with jax.named_scope("bk_clipped_sum"):
        def wgrad(key, ds):
            path, kind, _ = parse_key(key)
            wpath = path + "/w"
            w = flat_params[wpath]
            vocab = w.shape[-2] if kind == "emb" else 0
            return record_weighted_grad(
                key, acts_l[key], ds, unit_C[unit_of(wpath)], cache[key],
                policy.use_kernels, w.dtype, vocab, mesh=mesh, shard=shard)

        # streamed keys are absent from ``held``/``cache``: their grads landed
        # in flat_grads during phase 2 and nothing of theirs survives to here
        rec_keys = [k for k in active_taps
                    if k not in stream_keys and held[k] is None]
        for key in active_taps:
            if held.get(key) is None:
                continue
            with tap_scope(key):
                ds_in = (held[key] if tape_pol[key] == "bf16"
                         else load_record(held[key], tap_struct[key].dtype))
                flat_grads[_tap_w(key)] = wgrad(key, ds_in)
        if rec_keys:
            # 'recompute' taps re-derive their weighted gradients with a
            # REWEIGHTED-LOSS backward (the paper's module 2b'): for clip
            # unit u, grad_w sum_i C_i^(u) L_i == sum_i C_i^(u) g_i[w] — one
            # standard backward w.r.t. the chunk's ghost weights only, with
            # the batch re-run through an UNTAPPED, non-collecting Tape.
            # Nothing from phase 1 survives for these taps: their cotangents
            # died at the norms, their activation records are never consumed
            # in phase 3, and the re-derivation backward remats at the
            # models' own jax.checkpoint scan-block boundaries. (A per-chunk
            # tap-cotangent transpose was measured strictly worse: its zero
            # tangents for every other tap materialize as full-size scan
            # inputs.)
            token = unit_C[0]
            for u in range(len(res.units)):
                rec_u = [k for k in rec_keys if unit_of(_tap_w(k)) == u]
                if not rec_u:
                    continue
                nch = max(1, min(int(policy.tape_chunks), len(rec_u)))
                size = -(-len(rec_u) // nch)
                C_u = jax.lax.stop_gradient(unit_C[u])
                for lo in range(0, len(rec_u), size):
                    group = rec_u[lo:lo + size]
                    wpaths = [_tap_w(k) for k in group]

                    def reweighted(wsub):
                        merged = dict(flat_params)
                        merged.update(psp0)
                        merged.update(wsub)
                        losses = apply_fn(unflatten(merged), batch,
                                          Tape({}, collect=False))
                        return jnp.sum(losses * C_u)

                    # the backward's cotangent seed goes through an
                    # optimization barrier CHAINED on the previous chunk's
                    # grads (the clip factors for the first): phase 2
                    # completes — its cotangents freed — before any
                    # re-derivation runs, and the sweeps run one at a time so
                    # their live sets never overlap
                    seed, _ = jax.lax.optimization_barrier(
                        (jnp.ones_like(loss_sum), token))
                    _, vjp_w = jax.vjp(reweighted,
                                       {p: flat_params[p] for p in wpaths})
                    (gw,) = vjp_w(seed)
                    for p in wpaths:
                        flat_grads[p] = gw[p].astype(flat_params[p].dtype)
                    token = flat_grads[wpaths[-1]]
        for p in psp_active:
            g = g_psp[p]
            flat_grads[p] = jnp.einsum("b...,b->...", g.astype(F32),
                                       unit_C[unit_of(p)]).astype(
                                           flat_params[p].dtype)
        for p in res.frozen:
            flat_grads[p] = jnp.zeros_like(flat_params[p])

    if mask is not None:   # observability reports REAL samples only
        losses = losses[:B_real]
        sq = [s[:B_real] for s in sq]
        unit_norms = [n[:B_real] for n in unit_norms]
        unit_C = [c[:B_real] for c in unit_C]
    return flat_grads, norm_aux(res, losses, sq, unit_norms, unit_C)


def monolithic_clipped_sum(apply_fn, params, batch, cfg, mesh=None):
    """The pre-residency reference: ONE jax.vjp whose tap cotangents all
    stay live from phase 1 through phase 3. Kept as the parity oracle the
    streamed engine is tested against (tape_policy='native' must match it
    bitwise; 'recompute'/'bf16'/'int8' within documented tolerances) — not
    wired to any production path."""
    policy = as_policy(cfg)
    assert policy.mode in BK_MODES, policy.mode
    B = batch_size_of(batch)
    shard = batch_shard(mesh, B)
    ba = shard[0] if shard else ()
    flat_params = flatten(params)
    tap_struct = tap_structs(apply_fn, params, batch)
    _, psp_paths = split_param_paths(params, tap_struct)
    res = resolve_policy(policy, flat_params)

    active_taps = sorted(k for k in tap_struct if _tap_w(k) not in res.frozen)
    psp_active = [p for p in psp_paths if p not in res.frozen]
    taps0 = {k: jnp.zeros(tap_struct[k].shape, tap_struct[k].dtype)
             for k in active_taps}
    psp0 = {p: jnp.broadcast_to(flat_params[p], (B,) + flat_params[p].shape)
            for p in psp_active}
    if shard:
        psp0 = {p: _constrain(v, mesh, _bspec(v.ndim, 0, ba))
                for p, v in psp0.items()}

    def run(taps, psp):
        merged = dict(flat_params)
        merged.update(psp)
        tape = Tape(taps)
        losses = apply_fn(unflatten(merged), batch, tape)
        return jnp.sum(losses), (losses, tape.acts)

    with jax.named_scope("bk_taps"):
        loss_sum, vjp_fn, (losses, acts) = jax.vjp(run, taps0, psp0,
                                                   has_aux=True)
        ds_taps, g_psp = vjp_fn(jnp.ones_like(loss_sum))

    unit_of = lambda p: res.unit_of[p]
    with jax.named_scope("bk_norms"):
        sq = [jnp.zeros((B,), F32) for _ in res.units]
        cache = {}
        for key in active_taps:
            wpath = _tap_w(key)
            with tap_scope(key):
                nk, cached = record_sq_norm(
                    key, acts[key], ds_taps[key], policy.mode,
                    policy.use_kernels, res.method_for(wpath), mesh=mesh,
                    shard=shard)
            cache[key] = cached
            u = unit_of(wpath)
            sq[u] = sq[u] + nk
        for p in psp_active:
            g = g_psp[p].astype(F32)
            u = unit_of(p)
            sq[u] = sq[u] + jnp.sum(g * g, axis=tuple(range(1, g.ndim)))
        if shard:
            sq = [_constrain(s, mesh, P(ba)) for s in sq]
        unit_norms, unit_C = unit_clip_factors(res, sq)

    flat_grads = {}
    with jax.named_scope("bk_clipped_sum"):
        for key in active_taps:
            path, kind, _ = parse_key(key)
            wpath = path + "/w"
            w = flat_params[wpath]
            vocab = w.shape[-2] if kind == "emb" else 0
            with tap_scope(key):
                flat_grads[wpath] = record_weighted_grad(
                    key, acts[key], ds_taps[key], unit_C[unit_of(wpath)],
                    cache[key], policy.use_kernels, w.dtype, vocab,
                    mesh=mesh, shard=shard)
        for p in psp_active:
            g = g_psp[p]
            flat_grads[p] = jnp.einsum("b...,b->...", g.astype(F32),
                                       unit_C[unit_of(p)]).astype(
                                           flat_params[p].dtype)
        for p in res.frozen:
            flat_grads[p] = jnp.zeros_like(flat_params[p])

    return flat_grads, norm_aux(res, losses, sq, unit_norms, unit_C)


def bk_private_grad(apply_fn, params, batch, rng, cfg, step=None, mesh=None,
                    pspecs=None):
    """Private gradient via Book-Keeping: clipped sum + noise + 1/B scale.
    ``step`` feeds stateful noise mechanisms (tree aggregation raises when it
    is omitted); the default Gaussian ignores it. ``mesh``/``pspecs`` lower
    the clipped sum batch-sharded and draw phase-4 noise shard-local.
    Returns (grads matching the params tree, aux)."""
    policy = as_policy(cfg)
    B = batch_size_of(batch)
    flat_sums, aux = bk_clipped_sum(apply_fn, params, batch, policy,
                                    mesh=mesh, rng=rng)
    # ---- phase 4: noise (sigma * sigma_scale_u * composed S per unit) + scale
    res = resolve_policy(policy, flatten(params))
    flat_grads = finalize_noise(policy, res, flat_sums, rng, float(B), step,
                                mesh=mesh, pspecs=pspecs)
    return unflatten(flat_grads), aux
