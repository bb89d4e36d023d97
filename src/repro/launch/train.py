"""End-to-end DP training driver: data pipeline -> BK private gradient ->
optimizer -> checkpoint/restart, with preemption + heartbeat guards.

    PYTHONPATH=src python -m repro.launch.train --arch qwen2-1.5b --smoke \
        --steps 50 --batch 8 --seq 64 --epsilon 3.0

Accepts a bare DPConfig or a named PrivacyPolicy preset (``--policy``; the
default 'auto' picks the arch's registered preset when one exists, e.g.
deepseek-moe-16b's expert/router/dense group-wise split). Before the first
step the driver can autotune the fused-kernel block sizes for the model's
actual tap shapes (``--autotune``, measured via kernels.dispatch.autotune and
pinned with override_blocks).

``--optimizer ftrl`` trains with momentum DP-FTRL: the policy's noise
mechanism is switched to binary-tree aggregation (depth sized to the run's
horizon), ``--restart-every N`` restarts both the optimizer anchor and the
noise tree every N steps, and ``--tree-completion`` applies the
honest-restart variance correction at each boundary.

Runs on whatever devices exist: the CPU for tests (Pallas kernels in
interpret mode), a TPU chip or host with the kernels compiled by Mosaic
(pass --mesh data,model sizes). ``chip_smoke.py`` at the checkout root is
the end-to-end check on a chip.

JAX's persistent compile cache lives where ``JAX_COMPILATION_CACHE_DIR``
says, and otherwise at ``<checkout>/.jax_cache`` (``use_compile_cache``)."""
from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import math
import os
import pathlib
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import run_state as rs
from repro.configs.base import TrainConfig
from repro.configs.registry import (build, get_config, get_policy, has_policy,
                                    list_archs, list_policies, smoke_config)
from repro.core.accounting import PrivacyLedger, budget_for
from repro.core.bk import DPConfig
from repro.core.policy import as_policy, resolve_policy
from repro.core.tape import Tape, parse_key
from repro.data.pipeline import Pipeline, PipelineConfig
from repro.launch import sharding as sh
from repro.launch.mesh import make_train_mesh
from repro.launch.steps import (TrainState, init_train_state,
                                make_train_step)
from repro.optim.optimizers import make_optimizer
from repro.optim.schedules import make_schedule
from repro.runtime.fault_injection import maybe_fault
from repro.runtime.fault_tolerance import (CheckpointManager, Heartbeat,
                                           PreemptionGuard)
from repro.utils.tree import flatten


CHECKOUT = pathlib.Path(__file__).resolve().parents[3]


def use_compile_cache() -> str:
    """-> the persistent compile cache directory ('' when off). JAX reads
    ``JAX_COMPILATION_CACHE_DIR`` itself; without it an accelerator's
    cache goes to one fixed path in the checkout, so that a rerun of the
    same program finds what the last one compiled. CPU programs are not
    cached: their entries are tied to the host's CPU features."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR", "")
    if not path and jax.default_backend() != "cpu":
        path = str(CHECKOUT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def resolve_dp(arch: str, policy_name: str, mode: str, clipping: str,
               sigma: float, log=print):
    """--policy/--mode/--clipping/--sigma -> DPConfig or PrivacyPolicy."""
    if policy_name == "auto":
        policy_name = arch if has_policy(arch) else ""
    if not policy_name:
        return DPConfig(mode=mode, clipping=clipping, sigma=sigma)
    dp = get_policy(policy_name, mode=mode, sigma=sigma)
    if clipping != "automatic":
        log(f"note: --clipping {clipping} is IGNORED — the policy preset "
            f"{policy_name!r} defines clipping per group (pass --policy '' "
            "for a flat DPConfig)")
    log(f"policy preset {policy_name!r}: "
        + ", ".join(f"{g.name}({g.scope}{'' if g.trainable else ',frozen'}"
                    f" R={g.R})" for g in dp.groups))
    return dp


# ---------------------------------------------------------- autotune warmup
def _block_candidates(blocks: tuple, align: int = 8) -> list:
    """Candidate block tuples around the analytic choice: {x/2, x, 2x} per
    knob (aligned, deduped, cartesian, capped)."""
    axes = []
    for name, val in blocks:
        a = 128 if name == "block_v" else align
        vals = sorted({max(a, (val // 2) // a * a), val,
                       max(a, (val * 2) // a * a)})
        axes.append([(name, v) for v in vals])
    cands = [()]
    for axis in axes:
        cands = [c + (nv,) for c in cands for nv in axis]
    return cands[:16]


def _synth(struct, rng, vocab: int = 0):
    """Concrete array for one eval_shape leaf (ids get valid vocab range)."""
    if jnp.issubdtype(struct.dtype, jnp.integer):
        return jax.random.randint(rng, struct.shape, 0, max(vocab, 2),
                                  dtype=struct.dtype)
    if struct.dtype == jnp.bool_:
        return jnp.ones(struct.shape, jnp.bool_)
    return jax.random.normal(rng, struct.shape, struct.dtype)


def autotune_warmup(apply_fn, params, batch, dp, log=print) -> int:
    """Measured-autotune the fused kernels on THIS model's tap shapes, once,
    outside jit, and pin the winners via ``dispatch.override_blocks`` so
    every subsequent plan (train step, kernel_report) uses them.

    ROADMAP PR-1 follow-up: ``dispatch.autotune`` existed but nothing ran it
    automatically. Returns the number of (tap-shape, phase) cells tuned."""
    from repro.kernels import dispatch
    from repro.kernels import ops as kops

    policy = as_policy(dp)
    if not policy.use_kernels:
        return 0

    def shape_run(p, b):
        tape = Tape(None)
        apply_fn(p, b, tape)
        return tape.tap_zeros, tape.acts

    taps, acts = jax.eval_shape(shape_run, params, batch)
    flat_params = flatten(params)
    res = resolve_policy(policy, flat_params)

    runners = {  # (phase, kind, method) -> (ops fn, needs C, static knobs)
        ("norm", "mm", "ghost"): kops.ghost_norm_mm,
        ("norm", "mm", "direct"): kops.direct_norm_mm,
        ("norm", "emb", "ghost"): kops.ghost_norm_emb,
        ("grad", "mm", "direct"): kops.clipped_grad_mm,
        ("grad", "emb", "scatter"): kops.clipped_grad_emb,
    }

    rng = jax.random.PRNGKey(0)
    tuned, seen = 0, set()
    for key in sorted(acts):
        path, kind, _ = parse_key(key)
        wpath = path + "/w"
        if wpath in res.frozen or kind == "moe":  # moe: no kernel to tune
            continue
        method = res.method_for(wpath)
        a_struct = acts[key]
        ds_struct = taps[key]
        vocab = flat_params[wpath].shape[-2] if kind == "emb" else 0
        cell = (kind, tuple(a_struct.shape), tuple(ds_struct.shape), vocab,
                method)
        if cell in seen:
            continue
        seen.add(cell)

        B = ds_struct.shape[-3]
        act = _synth(acts[key], rng, vocab)
        ds = _synth(ds_struct, rng)
        C = jnp.ones((B,), jnp.float32)

        for phase in ("norm", "grad"):
            if phase == "norm":
                plan = dispatch.norm_plan(kind, a_struct.shape,
                                          ds_struct.shape, policy.mode,
                                          method)
                args = (act, ds)
            else:
                plan = dispatch.grad_plan(kind, a_struct.shape,
                                          ds_struct.shape, vocab)
                args = (act, C, ds)
            cands = _block_candidates(plan.blocks)
            fn = runners.get((phase, kind, plan.method))
            if plan.impl != "kernel" or fn is None or len(cands) <= 1:
                continue
            if phase == "grad" and kind == "emb":
                fn = functools.partial(fn, vocab=vocab)  # static under jit
            knobs = tuple(name for name, _ in plan.blocks)
            run = jax.jit(fn, static_argnames=knobs)
            best = dispatch.autotune(run, cands, *args, default=plan.blocks)
            dispatch.override_blocks(phase, kind, a_struct.shape,
                                     ds_struct.shape, best,
                                     mode=policy.mode, vocab=vocab,
                                     method=method)
            tuned += 1
            if best != plan.blocks:
                log(f"autotune {key}/{phase}: {dict(plan.blocks)} -> "
                    f"{dict(best)}")
    log(f"autotune warmup: {tuned} kernel cells tuned, pinned via "
        "override_blocks")
    return tuned


def train(model_cfg, tc: TrainConfig, dp, log=print,
          dataset_size: int = 0, target_epsilon: float = 0.0,
          delta: float = 1e-5, summary_out=None):
    model = build(model_cfg)
    if tc.tape or tc.tape_chunks:
        # --tape/--tape-chunks override whatever the DPConfig / preset set
        # (both config types carry the fields, so replace works on either)
        dp = dataclasses.replace(
            dp, **({"tape_policy": tc.tape} if tc.tape else {}),
            **({"tape_chunks": tc.tape_chunks} if tc.tape_chunks else {}))
    if tc.clipping_scope:
        # --clipping-scope re-scopes every trainable group (with_scope);
        # 'layer' turns each param path into its own clip unit and the BK
        # backward streams — one pass, nothing book-kept between phases
        from repro.core.policy import with_scope
        dp = with_scope(dp, tc.clipping_scope)
        log(f"clipping scope: {tc.clipping_scope}"
            + (" (per-path clip units; streamed one-pass backward)"
               if tc.clipping_scope == "layer" else ""))
    policy = as_policy(dp)
    if tc.tape or tc.tape_chunks:
        log(f"tape residency: policy={policy.tape_policy} "
            f"chunks={policy.tape_chunks}")
    if target_epsilon > 0 and dataset_size > 0 and policy.sigma == 0.0:
        # Tree-aggregation releases (DP-FTRL, or ANY policy configured with
        # noise='tree') get no subsampling amplification — the SGM curve
        # under-reports their epsilon, so calibrate against the tree
        # accountant whenever tree noise will actually run
        tree_release = tc.optimizer == "ftrl" or policy.noise == "tree"
        mechanism = "tree" if tree_release else "sgm"
        budget = budget_for(target_epsilon, delta, tc.global_batch,
                            dataset_size,
                            tc.steps * tc.global_batch / dataset_size,
                            mechanism=mechanism,
                            restart_every=(tc.restart_every
                                           or policy.noise_restart_every))
        dp = dataclasses.replace(dp, sigma=budget.sigma)
        log(f"calibrated sigma={budget.sigma:.3f} for "
            f"eps={budget.epsilon:.2f} ({mechanism} accountant)")
        if any(g.sigma_scale != 1.0 for g in policy.groups):
            log("WARNING: sigma was calibrated with the FLAT single-sigma "
                "accountant, but this policy sets per-group sigma_scale — "
                "the true joint-bound epsilon differs (larger when any "
                "scale < 1). Re-check with compute_epsilon("
                "resolved.noise_multipliers(), ...) (README 'Accounting "
                "caveats').")

    if tc.optimizer != "ftrl" and (tc.restart_every or tc.tree_completion
                                   or tc.ftrl_momentum):
        # silently ignoring these would leave the user believing they
        # configured tree restarts while plain gaussian noise runs
        raise ValueError(
            "--restart-every/--tree-completion/--ftrl-momentum are DP-FTRL "
            f"knobs; pass --optimizer ftrl (got {tc.optimizer!r})")
    if tc.tree_completion and tc.restart_every <= 0:
        raise ValueError("--tree-completion corrects the noise at epoch "
                         "boundaries; pass --restart-every N (> 0) with it")
    if tc.optimizer == "ftrl" and tc.lr_schedule != "constant":
        log(f"WARNING: FTRL rescales the WHOLE gradient prefix by the "
            f"current lr — a decaying schedule ({tc.lr_schedule!r}) drags "
            "the iterate back toward its anchor and undoes most of "
            "training. Use lr_schedule='constant' (the CLI driver forces "
            "it for --optimizer ftrl).")
    ftrl_restart = tc.restart_every
    if tc.optimizer == "ftrl" and policy.mode != "nonprivate":
        # FTRL consumes the NOISY GRADIENT PREFIX: switch the policy to the
        # tree-aggregation mechanism with depth sized to the actual horizon
        # so each add() pays only what it needs. A policy that already
        # configures tree noise keeps its own knobs (never silently
        # overridden); either way the optimizer anchor and the noise tree
        # must restart at the SAME boundary, so conflicts are an error.
        from repro.core.noise import next_pow2
        pol = as_policy(dp)
        pol_tree = pol.noise == "tree"
        if pol_tree and pol.noise_restart_every and tc.restart_every and \
                pol.noise_restart_every != tc.restart_every:
            raise ValueError(
                f"policy sets noise_restart_every={pol.noise_restart_every} "
                f"but --restart-every={tc.restart_every}: the FTRL anchor "
                "and the noise tree must restart together")
        ftrl_restart = tc.restart_every or \
            (pol.noise_restart_every if pol_tree else 0)
        completion = tc.tree_completion or \
            (pol.noise_completion if pol_tree else False)
        horizon = ftrl_restart if ftrl_restart > 0 else tc.steps
        depth = (pol.noise_depth if pol_tree and pol.noise_depth
                 else max(next_pow2(horizon).bit_length(), 1))
        dp = dataclasses.replace(pol, noise="tree", noise_depth=depth,
                                 noise_restart_every=ftrl_restart,
                                 noise_completion=completion)
        policy = dp
        log(f"DP-FTRL: tree noise depth={policy.noise_depth} "
            f"restart_every={ftrl_restart or 'never'} "
            f"completion={completion}")

    # validate the tree horizon upfront for EVERY optimizer: inside the
    # jitted step the index is traced, so the mechanism's own concrete-step
    # guard can never fire — past 2^depth - 1 the prefix would collapse and
    # increments would subtract released noise with no error
    final_policy = as_policy(dp)
    if final_policy.noise == "tree" and final_policy.noise_depth and \
            not final_policy.noise_restart_every and \
            tc.steps > (1 << final_policy.noise_depth) - 1:
        raise ValueError(
            f"noise_depth={final_policy.noise_depth} covers only "
            f"{(1 << final_policy.noise_depth) - 1} steps but the run has "
            f"{tc.steps}; raise noise_depth or set restarts")
    # surface mechanism config errors before init; the bound instance also
    # carries the restorable noise state the RunState checkpoint persists
    mech = final_policy.mechanism()

    opt_kw = ({"momentum": tc.ftrl_momentum,
               "restart_every": ftrl_restart}
              if tc.optimizer == "ftrl" else {})
    opt = make_optimizer(tc.optimizer,
                         make_schedule(tc.lr_schedule, tc.lr, tc.warmup, tc.steps),
                         weight_decay=tc.weight_decay, **opt_kw)
    pipe = Pipeline(model_cfg, PipelineConfig(tc.global_batch, tc.seq_len,
                                              seed=tc.seed))

    guard = PreemptionGuard()

    def on_stall(report):
        # a hung step can't be checkpointed from here (its state is inside
        # the collective), but requesting a stop means the loop — if it
        # ever returns — force-saves before exit instead of running on
        log(report.describe() + "; requesting graceful stop + checkpoint")
        guard.request_stop()

    # the watchdog starts after the first step has run: autotune and the
    # step's first compile come before it and may take longer than timeout_s
    hb = None
    mgr = (CheckpointManager(tc.checkpoint_dir, every=tc.checkpoint_every,
                             keep=tc.keep_checkpoints)
           if tc.checkpoint_dir else None)

    # ---- privacy ledger (absolute steps accounted, resumed verbatim) --------
    mech_kind = "tree" if final_policy.noise == "tree" else "sgm"
    sample_rate = (tc.global_batch / dataset_size if dataset_size > 0
                   else 1.0)
    ledger_restart = ftrl_restart or final_policy.noise_restart_every
    participations = (max(1, math.ceil(tc.steps * tc.global_batch
                                       / dataset_size))
                      if dataset_size > 0 else 1)
    ledger_kw = dict(sigma=float(final_policy.sigma),
                     sample_rate=sample_rate, mechanism=mech_kind,
                     restart_every=ledger_restart,
                     participations=participations)
    ledger = PrivacyLedger()
    fingerprint = rs.config_fingerprint(tc, final_policy, ftrl_restart)

    # ---- init or resume -----------------------------------------------------
    start = 0
    params = model.init(jax.random.PRNGKey(tc.seed))
    opt_state = opt.init(params)
    base_rng = jax.random.PRNGKey(tc.seed + 1)
    if mgr is not None:
        state0, step0, meta0 = mgr.resume(template={"params": params,
                                                    "opt": opt_state,
                                                    "step": np.asarray(0),
                                                    "rng": base_rng})
        if state0 is not None:
            # validates noise/pipeline/config against the checkpoint and
            # raises on privacy-critical drift; restores the spent ledger
            ledger = rs.check_resume(meta0, mech, pipe, fingerprint, log=log)
            params, opt_state = state0["params"], state0["opt"]
            base_rng = state0["rng"]
            start = step0 + 1
            log(f"resumed from step {step0} "
                f"(ledger covers {ledger.recorded_to} steps)")

    # ---- warmup: measured kernel autotune on the real tap shapes ------------
    if tc.autotune == "on" or (tc.autotune == "auto"
                               and jax.default_backend() != "cpu"):
        autotune_warmup(model.apply, params, pipe.batch(0), dp, log=log)

    # ---- the mesh-native donated step ---------------------------------------
    # One jitted (state, batch) -> (state, loss): explicit in/out shardings
    # from the partition-spec tables, the whole TrainState donated, BK
    # lowered batch-sharded with shard-local noise (launch.steps).
    mesh = make_train_mesh(tc.mesh_data, tc.mesh_model)
    if len(mesh.devices.flat) > 1:
        log(f"mesh {dict(mesh.shape)} over {mesh.devices.size} devices")
    step_fn, state_sh, batch_sh = make_train_step(
        model.apply, params, opt, tc.optimizer, dp, tc.microbatch, mesh,
        pipe.batch(0))
    jitted = jax.jit(step_fn, in_shardings=(state_sh, batch_sh),
                     out_shardings=(state_sh, None), donate_argnums=(0,))
    # base_rng is the CHECKPOINTED key on resume: per-step keys fold the
    # absolute step into it, so restoring it replays the interrupted run's
    # exact noise sequence (the bitwise-restart guarantee)
    state = init_train_state(params, opt_state, start, base_rng, state_sh)
    # compiled ahead of the loop: the compile is timed apart from the steps,
    # and the program can be checked for its Pallas kernels
    t0 = time.time()
    with mesh:
        compiled = jitted.lower(
            state, jax.device_put(pipe.batch(start), batch_sh)).compile()
    n_kernels = compiled.as_text().count("tpu_custom_call")
    log(f"step compiled in {time.time() - t0:.1f}s "
        f"({n_kernels} Pallas kernel calls)")

    def snapshot(s: TrainState, step: int) -> dict:
        return {"params": s.params, "opt": s.opt_state,
                "step": np.asarray(step), "rng": s.rng}

    def run_meta() -> dict:
        return rs.pack_meta(mech, ledger, pipe, fingerprint)

    # losses stay on device; the buffer drains every log_every steps and at
    # exit — no step blocks on a device->host sync
    losses, pending = [], []
    log_every = max(1, tc.log_every)
    t_flush = time.time()

    def flush(step: int):
        nonlocal t_flush
        if not pending:
            return
        n = len(pending)
        losses.extend(float(x) for x in jax.device_get(pending))
        pending.clear()
        dt = (time.time() - t_flush) / n
        t_flush = time.time()
        log(f"step {step:5d} loss {losses[-1]:.4f} ({dt:.2f}s/step over "
            f"last {n})")

    with mesh:
        for step in range(start, tc.steps):
            maybe_fault("step", step)  # crash/preemption injection (tests)
            batch = jax.device_put(pipe.batch(step), batch_sh)
            state, loss = compiled(state, batch)
            pending.append(loss)
            if hb is None:
                hb = Heartbeat(timeout_s=600.0, on_stall=on_stall)
            hb.beat(step)
            # every executed absolute step is accounted exactly once —
            # resumed replays are no-ops (ledger.record_to is idempotent)
            ledger.record_to(step + 1, **ledger_kw)
            saved = (mgr.maybe_save(step, snapshot(state, step),
                                    meta=run_meta())
                     if mgr is not None else False)
            if guard.should_stop():
                if mgr is not None and not saved:
                    mgr.maybe_save(step, snapshot(state, step), force=True,
                                   meta=run_meta())
                flush(step)
                log(f"{'preempted' if guard.signalled else 'stopped'} at "
                    f"step {step}"
                    + ("; checkpoint saved" if mgr is not None else ""))
                break
            if (step + 1) % log_every == 0 or step == tc.steps - 1:
                flush(step)
    flush(tc.steps - 1)
    if mgr is not None:
        mgr.wait()
    if hb is not None:
        hb.close()

    epsilon = None
    if final_policy.mode != "nonprivate" and ledger.recorded_to > 0:
        epsilon = ledger.epsilon(delta)
        log(f"privacy spent: eps={epsilon:.4g} (delta={delta:g}) over "
            f"{ledger.recorded_to} accounted steps "
            f"[{mech_kind}{' restarts' if ledger_restart else ''}]")
    if summary_out is not None:
        summary_out.update({
            "steps_done": ledger.recorded_to,
            "preempted": guard.signalled,
            "step_kernel_calls": n_kernels,
            "resumed_from": start,
            "epsilon": epsilon,
            "delta": delta,
            "params_sha256": rs.params_digest(state.params),
            "ledger": ledger.to_json(),
        })
    return jax.device_get(state.params), losses


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list_archs(), default="qwen2-1.5b")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced same-family config (CPU-sized)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--microbatch", type=int, default=0)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--optimizer", default="adamw",
                    help="sgd | adamw | lamb | adafactor | ftrl (DP-FTRL: "
                         "tree-aggregation noise, prefix-sum iterate)")
    ap.add_argument("--ftrl-momentum", type=float, default=0.0,
                    help="DP-FTRL momentum over noisy gradient prefixes")
    ap.add_argument("--restart-every", type=int, default=0,
                    help="DP-FTRL epoch restart period in steps (0 = never); "
                         "restarts the optimizer anchor AND the noise tree")
    ap.add_argument("--tree-completion", action="store_true",
                    help="Honaker completion: advance each epoch's tree to "
                         "the next power of two before restarting")
    ap.add_argument("--mode", default="bk-mixopt")
    ap.add_argument("--clipping", default="automatic")
    ap.add_argument("--sigma", type=float, default=0.0)
    ap.add_argument("--epsilon", type=float, default=0.0)
    ap.add_argument("--dataset-size", type=int, default=50000)
    ap.add_argument("--policy", default="auto",
                    help="PrivacyPolicy preset name; 'auto' = the arch's "
                         f"registered preset (known: {list_policies()}), "
                         "'' = flat DPConfig")
    ap.add_argument("--autotune", choices=["auto", "on", "off"],
                    default="auto",
                    help="measured kernel-block autotune at startup "
                         "(auto = on for non-CPU backends)")
    ap.add_argument("--tape", default="",
                    choices=["", "native", "bf16", "int8", "recompute",
                             "auto"],
                    help="tape residency for book-kept tap state between BK "
                         "phases 2-3: hold native, compressed (bf16/int8), "
                         "re-derive in phase 3 (recompute), or let the "
                         "dispatch planner pick per tap (auto); '' keeps "
                         "the policy preset's choice")
    ap.add_argument("--tape-chunks", type=int, default=0,
                    help="phase-3 re-derivation chunk count for recompute "
                         "taps (0 keeps the policy's)")
    ap.add_argument("--clipping-scope", default="",
                    choices=["", "flat", "group", "layer"],
                    help="re-scope every trainable group's clipping norm: "
                         "flat (one pool), group (per policy group), layer "
                         "(each param path its own clip unit — the BK "
                         "backward streams in one pass with nothing "
                         "book-kept); '' keeps the preset's scopes")
    ap.add_argument("--mesh", default="",
                    help="data,model axis sizes for the train mesh "
                         "(e.g. 4,2); default: all devices on 'data'")
    ap.add_argument("--log-every", type=int, default=10,
                    help="loss log + device->host flush period in steps")
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--out", default="",
                    help="write a json run summary (steps done, epsilon, "
                         "params sha256, ledger) — the CI crash/resume "
                         "stage compares these across runs")
    args = ap.parse_args()
    use_compile_cache()

    mesh_data, mesh_model = 0, 1
    if args.mesh:
        try:
            mesh_data, mesh_model = (int(x) for x in args.mesh.split(","))
        except ValueError:
            ap.error(f"--mesh wants 'data,model' ints, got {args.mesh!r}")

    mc = smoke_config(args.arch) if args.smoke else get_config(args.arch)
    mc = mc.with_(dtype="float32", param_dtype="float32") if args.smoke else mc
    tc = TrainConfig(global_batch=args.batch, microbatch=args.microbatch,
                     seq_len=args.seq, steps=args.steps, lr=args.lr,
                     optimizer=args.optimizer,
                     # FTRL rescales the whole prefix by lr_t: decay would
                     # pull the iterate back toward the anchor
                     lr_schedule=("constant" if args.optimizer == "ftrl"
                                  else TrainConfig.lr_schedule),
                     ftrl_momentum=args.ftrl_momentum,
                     restart_every=args.restart_every,
                     tree_completion=args.tree_completion,
                     policy=args.policy, autotune=args.autotune,
                     tape=args.tape, tape_chunks=args.tape_chunks,
                     clipping_scope=args.clipping_scope,
                     mesh_data=mesh_data, mesh_model=mesh_model,
                     log_every=args.log_every,
                     checkpoint_dir=args.ckpt_dir,
                     checkpoint_every=args.ckpt_every)
    dp = resolve_dp(args.arch, args.policy, args.mode, args.clipping,
                    args.sigma)
    summary = {}
    train(mc, tc, dp, dataset_size=args.dataset_size,
          target_epsilon=args.epsilon, summary_out=summary)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(summary, f, indent=2)
        print(f"summary written to {args.out}")
    if summary["steps_done"] < tc.steps and not summary["preempted"]:
        # only a real preemption (SIGTERM) may end a run early with status 0
        sys.exit(f"stopped after {summary['steps_done']} of {tc.steps} "
                 "steps without a preemption signal")


if __name__ == "__main__":
    main()
