"""Step builders: (arch x shape x mesh) -> jit-able function + abstract args
+ shardings. Used by the dry-run (lower/compile on ShapeDtypeStructs) and by
the real train/serve drivers.

``TrainState`` + ``make_train_step`` are the single source of truth for the
production train step: a mesh-lowered, donation-clean jitted function over
(state, batch) with explicit in/out shardings. The dryrun planner, the real
``launch.train`` driver, ``benchmarks.step_bench`` and the sharded tests all
build the same step through here."""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import SHAPES, ModelConfig, ShapeConfig
from repro.configs.registry import build, get_config, get_policy, has_policy
from repro.core.bk import BK_MODES, DPConfig
from repro.core.policy import as_policy, noise_leaf_fn, resolve_policy
from repro.data.synthetic import batch_spec
from repro.launch import sharding as sh
from repro.optim.accumulate import (accumulated_clipped_sum,
                                    accumulated_private_grad)
from repro.optim.optimizers import make_optimizer
from repro.utils.tree import flatten


@dataclass
class TrainState:
    """The donated unit of the train loop: everything a step consumes and
    produces. ``step`` is a () int32 on device; ``rng`` is the BASE key —
    each step folds its own index in, so the state never needs a host-side
    rng update and resume is bit-exact from (seed, step) alone."""
    params: dict
    opt_state: dict
    step: jax.Array
    rng: jax.Array


jax.tree_util.register_dataclass(
    TrainState, data_fields=("params", "opt_state", "step", "rng"),
    meta_fields=())


def init_train_state(params, opt_state, step: int, rng,
                     state_sh=None) -> TrainState:
    """Assemble the donated TrainState (fresh init or checkpoint resume),
    placing params/opt_state against the step's shardings when given.

    ``rng`` is the BASE key (raw uint32[2]); a resumed run passes the
    CHECKPOINTED key here verbatim — the step function folds the absolute
    step into it, so handing back the same base key replays the exact
    per-step key sequence the interrupted run would have used."""
    state = TrainState(params=params, opt_state=opt_state,
                       step=jnp.asarray(step, jnp.int32),
                       rng=jnp.asarray(rng, jnp.uint32))
    if state_sh is not None:
        state = TrainState(
            params=jax.device_put(state.params, state_sh.params),
            opt_state=jax.device_put(state.opt_state, state_sh.opt_state),
            step=state.step, rng=state.rng)
    return state


def make_train_step(apply_fn, params_like, opt, opt_name: str, dp,
                    microbatch: int, mesh, batch_like):
    """-> (train_step, state_shardings, batch_shardings).

    ``train_step(state, batch) -> (new_state, loss)`` is pure and built for

        jax.jit(train_step, in_shardings=(state_sh, batch_sh),
                out_shardings=(state_sh, None), donate_argnums=(0,))

    Inside: BK runs mesh-lowered (batch-sharded book-keeping, one psum per
    weighted grad), phase-4 noise is generated shard-local, and — whenever
    the optimizer has a fused per-leaf path — the noise-add and the
    optimizer update happen in ONE pass over the leaves, so no second
    full-parameter-size gradient tree is ever live. That pass (or the
    optimizer's update alone) runs in the ``jax.named_scope`` ``update``."""
    policy = as_policy(dp)
    state_sh = sh.named(mesh, sh.state_pspecs(opt_name, params_like, mesh))
    batch_sh = sh.named(mesh, sh.batch_pspecs(batch_like, mesh))
    flat_pspecs = sh.flat_param_pspecs(params_like, mesh)
    res = resolve_policy(policy, flatten(params_like))

    def train_step(state, batch):
        rng = jax.random.fold_in(state.rng, state.step)
        if policy.mode in BK_MODES and opt.update_leaves is not None:
            sums, aux, B = accumulated_clipped_sum(
                apply_fn, state.params, batch, policy, microbatch, mesh=mesh,
                rng=rng)
            with jax.named_scope("update"):
                leaf = noise_leaf_fn(policy, res, rng, float(B),
                                     step=state.step, mesh=mesh,
                                     pspecs=flat_pspecs)
                new_p, new_o = opt.update_leaves(
                    lambda path, p: leaf(path, sums[path]),
                    state.opt_state, state.params, state.step)
        else:
            grads, aux = accumulated_private_grad(
                apply_fn, state.params, batch, rng, policy, microbatch,
                state.step, mesh=mesh, pspecs=flat_pspecs)
            with jax.named_scope("update"):
                new_p, new_o = opt.update(grads, state.opt_state,
                                          state.params, state.step)
        new_state = TrainState(params=new_p, opt_state=new_o,
                               step=state.step + 1, rng=state.rng)
        return new_state, aux["loss"]

    return train_step, state_sh, batch_sh

# physical (micro) batch for train_4k, tuned so the per-device book-keeping
# footprint stays within v5e HBM (see EXPERIMENTS.md §Dry-run)
TRAIN_MICROBATCH = {
    # >= data-axis size (16) so the microbatch stays shardable over 'data'
    "llama3-405b": 16, "internvl2-26b": 16, "qwen3-14b": 16,
    "deepseek-moe-16b": 16, "moonshot-v1-16b-a3b": 16,
    "qwen2-1.5b": 32, "qwen2.5-3b": 32, "whisper-small": 32,
    "rwkv6-3b": 16, "hymba-1.5b": 16,
}
TRAIN_OPTIMIZER = {"llama3-405b": "adafactor"}
SUBQUADRATIC = ("ssm", "hybrid")


def skip_reason(cfg: ModelConfig, shape: ShapeConfig) -> Optional[str]:
    if shape.name == "long_500k" and cfg.family not in SUBQUADRATIC:
        return ("full-attention arch: 524k dense-KV decode is quadratic-cost/"
                "unbounded-KV by construction; run only for SSM/hybrid "
                "(DESIGN.md §4)")
    return None


@dataclass
class CellPlan:
    arch: str
    shape: str
    kind: str
    fn: Callable
    args: tuple                  # ShapeDtypeStructs
    in_shardings: tuple
    donate: tuple = ()
    note: str = ""

    def jitted(self):
        return jax.jit(self.fn, in_shardings=self.in_shardings,
                       donate_argnums=self.donate)

    def lower(self):
        mesh = None
        for sh in jax.tree_util.tree_leaves(self.in_shardings):
            if hasattr(sh, "mesh"):
                mesh = sh.mesh
                break
        if mesh is not None:
            with jax.set_mesh(mesh):
                return self.jitted().lower(*self.args)
        return self.jitted().lower(*self.args)


def _key_struct():
    return jax.ShapeDtypeStruct((2,), jnp.uint32)


def _params_struct(model):
    return jax.eval_shape(model.init, _key_struct())


def plan_cell(arch: str, shape_name: str, mesh, dp=None,
              microbatch: Optional[int] = None, cfg_patch: Optional[dict] = None,
              optimizer: Optional[str] = None,
              clipping_scope: str = "") -> CellPlan:
    """``dp`` is a DPConfig, a PrivacyPolicy, or None — None picks the
    arch's registered policy preset when one exists (group-wise planning),
    else the flat bk-mixopt DPConfig. ``clipping_scope`` re-scopes every
    trainable group (policy.with_scope) before planning — 'layer' plans the
    streamed one-pass backward (train cells only)."""
    cfg = get_config(arch)
    if cfg_patch:
        cfg = cfg.with_(**cfg_patch)
    shape = SHAPES[shape_name]
    reason = skip_reason(cfg, shape)
    if reason:
        raise LookupError(reason)
    model = build(cfg)
    params = _params_struct(model)
    pspec = sh.param_pspecs(params, mesh)
    psh = sh.named(mesh, pspec)

    if shape.kind == "train":
        # bk-mixopt IS the paper's algorithm at T=4096 (§3: large-T needs the
        # layerwise hybrid; base-BK's 2BT^2 Grams are the wrong branch here).
        # When the arch registers a PrivacyPolicy preset the dryrun grid
        # plans THAT (group-wise norm accumulators + per-unit clip factors
        # change the book-keeping HBM), not a flat DPConfig.
        policy_tag = ""
        if dp is None and has_policy(arch):
            dp = get_policy(arch, mode="bk-mixopt", sigma=1.0)
            policy_tag = f" policy={arch}({len(dp.groups)}g)"
        dp = dp or DPConfig(mode="bk-mixopt", clipping="automatic", sigma=1.0)
        if clipping_scope:
            from repro.core.policy import with_scope
            dp = with_scope(dp, clipping_scope)
            policy_tag += f" scope={clipping_scope}"
        mb = microbatch or TRAIN_MICROBATCH.get(arch, 16)
        opt_name = optimizer or TRAIN_OPTIMIZER.get(arch, "adamw")
        opt = make_optimizer(opt_name, lambda s: jnp.asarray(1e-4, jnp.float32))
        bspec = batch_spec(cfg, shape.global_batch, shape.seq_len,
                           dtype=cfg.dtype)
        ostate = jax.eval_shape(opt.init, params)
        step_fn, state_sh, bsh = make_train_step(
            model.apply, params, opt, opt_name, dp, mb, mesh, bspec)
        state = TrainState(params=params, opt_state=ostate,
                           step=jax.ShapeDtypeStruct((), jnp.int32),
                           rng=_key_struct())
        return CellPlan(
            arch, shape_name, "train", step_fn, (state, bspec),
            (state_sh, bsh), donate=(0,),
            note=f"dp={as_policy(dp).mode} micro={mb} opt={opt_name}"
                 f"{policy_tag}")

    if shape.kind == "prefill":
        bspec = batch_spec(cfg, shape.global_batch, shape.seq_len,
                           dtype=cfg.dtype)
        bsh = sh.named(mesh, sh.batch_pspecs(bspec, mesh))
        if cfg.family == "encdec":
            fn = lambda p, b: model.prefill(p, b["frames"], b["tokens"])
        elif cfg.family == "vlm":
            fn = lambda p, b: model.prefill(p, b["tokens"], b["patches"])
        else:
            fn = lambda p, b: model.prefill(p, b["tokens"])
        return CellPlan(arch, shape_name, "prefill", fn, (params, bspec),
                        (psh, bsh))

    # decode
    B, S = shape.global_batch, shape.seq_len
    if cfg.family == "encdec":
        cache = jax.eval_shape(lambda: model.init_cache(B, S, Tf=S))
    else:
        cache = jax.eval_shape(lambda: model.init_cache(B, S))
    csh = sh.named(mesh, sh.cache_pspecs(cache, mesh))
    toks = jax.ShapeDtypeStruct((B,), jnp.int32)
    pos = jax.ShapeDtypeStruct((), jnp.int32)

    def serve_step(p, c, t, i):
        return model.decode_step(p, c, t, i)

    tsh = sh.named(mesh, sh.batch_pspecs(toks, mesh))
    return CellPlan(arch, shape_name, "decode", serve_step,
                    (params, cache, toks, pos), (psh, csh, tsh, None),
                    donate=(1,))
