#!/usr/bin/env python3
"""End-to-end check that the DP training step runs on a TPU.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # data-parallel mesh over four chips

Model: qwen2-1.5b at its published widths (d 1536, 12 heads / 2 KV heads of
128, d_ff 8960, vocab 151936, bf16) with its depth cut from 28 to 4 layers:
with AdamW's f32 moments the full depth does not fit one 16 GB v5e
(~1.78 B params -> ~14 GB of moments alone). Weights are random, from
``--seed``.

One chip, two phases in this one process:

  train   ``repro.launch.train.train`` for ``--steps`` steps: bk-mixopt under
          the registered vocab/trunk policy, sigma 1.0, batch 4 x seq 512,
          kernel autotune off. Fails unless every step ran, every loss is
          finite and the compiled step calls Pallas kernels.
  parity  one private gradient at sigma 0 on one batch through
          ``PrivacyEngine``, with the kernels and with the jnp reference.
          Fails when the clipped-gradient sum or the per-sample norms differ
          by more than PARITY_TOL, or when the kernel program holds no
          Pallas kernel (``tpu_custom_call``).

``--chips 4`` runs only the data-parallel path: the same training run on a
(4, 1) mesh and on one device; losses and final params must agree within
MESH_LOSS_RTOL / MESH_PARAM_TOL.

The last line of stdout is one JSON object: ``{"ok": true, "device":
{"platform", "kind", "count"}}``. Without a TPU it exits non-zero and prints
no result. JAX's compile cache goes where JAX_COMPILATION_CACHE_DIR says,
else to ``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import sys

ROOT = os.path.dirname(os.path.abspath(__file__))

ARCH = "qwen2-1.5b"
LAYERS = 4            # of 28: what fits one v5e with AdamW (see above)

# bf16 keeps 8 significant bits (unit roundoff 2^-8 ~ 3.9e-3). The kernels
# upcast each block to f32 and keep the clip factors in f32; the jnp
# reference rounds the clip factors to bf16 before its weighted-grad einsum,
# and the TPU runs its f32 Gram contractions at default (bf16-pass)
# precision. So the two paths sit a few roundoffs apart: 2e-2 is ~5 of them.
PARITY_TOL = 2e-2
# one device vs a (4, 1) mesh: the weighted-grad sums meet in a cross-chip
# psum instead of one in-kernel loop, so bf16 grads may differ by an ulp;
# through AdamW that moves a param by at most ~2 lr a step when it flips an
# update's sign, on top of two bf16 ulps of the leaf's scale.
MESH_LOSS_RTOL = 1e-2
MESH_PARAM_REL = 2.0 ** -6


def log(msg: str) -> None:
    print(msg, flush=True)


def model_config():
    from repro.configs.registry import get_config
    return get_config(ARCH).with_(n_layers=LAYERS)


def run_train(cfg, batch: int, seq: int, steps: int, seed: int,
              mesh_data: int = 0, lr: float = 3e-4):
    """-> (host params, losses, summary) of one training run."""
    from repro.configs.base import TrainConfig
    from repro.launch.train import resolve_dp, train

    dp = resolve_dp(ARCH, "auto", "bk-mixopt", "automatic", 1.0, log=log)
    tc = TrainConfig(global_batch=batch, seq_len=seq, steps=steps, lr=lr,
                     seed=seed, autotune="off", log_every=1,
                     mesh_data=mesh_data)
    summary = {}
    params, losses = train(cfg, tc, dp, log=log, summary_out=summary)
    return params, losses, summary


def phase_train(cfg, batch: int, seq: int, steps: int, seed: int) -> None:
    _, losses, summary = run_train(cfg, batch, seq, steps, seed)
    log(f"train: steps_done {summary['steps_done']} of {steps}, losses "
        f"{losses}, {summary['step_kernel_calls']} Pallas kernel calls "
        "in the step")
    if summary["steps_done"] != steps or len(losses) != steps:
        raise RuntimeError(f"train ran {summary['steps_done']} of {steps} "
                           "steps")
    if not all(math.isfinite(x) for x in losses):
        raise RuntimeError(f"non-finite loss: {losses}")
    if summary["step_kernel_calls"] == 0:
        raise RuntimeError("the compiled train step calls no Pallas kernel")


def _leaf_rel(got, ref) -> float:
    """max |got - ref| / max |ref| over one leaf (f32 on device)."""
    import jax.numpy as jnp
    got, ref = got.astype(jnp.float32), ref.astype(jnp.float32)
    return float(jnp.max(jnp.abs(got - ref))
                 / jnp.maximum(jnp.max(jnp.abs(ref)), 1e-30))


def phase_parity(cfg, batch: int, seq: int, seed: int) -> dict:
    """Kernel vs jnp private gradient at sigma 0 -> the max relative diffs."""
    import jax
    import jax.numpy as jnp

    from repro.configs.registry import build, get_policy
    from repro.core.engine import PrivacyEngine
    from repro.data.pipeline import Pipeline, PipelineConfig

    model = build(cfg)
    params = model.init(jax.random.PRNGKey(seed))
    data = Pipeline(cfg, PipelineConfig(batch, seq, seed=seed)).batch(0)
    rng = jax.random.PRNGKey(seed + 1)
    out = {}
    for use_kernels in (True, False):
        policy = dataclasses.replace(
            get_policy(ARCH, mode="bk-mixopt", sigma=0.0),
            use_kernels=use_kernels)
        grad = jax.jit(PrivacyEngine(model.apply, policy).grad)
        compiled = grad.lower(params, data, rng).compile()
        calls = compiled.as_text().count("tpu_custom_call")
        grads, aux = compiled(params, data, rng)
        # per clip unit: the total norm is dominated by the LM head's,
        # which runs on jnp either way (no ghost tile fits its vocab)
        out[use_kernels] = (grads, aux["group_norms"], calls)
        log(f"parity: use_kernels={use_kernels}: {calls} Pallas kernel "
            "calls in the program")
    (gk, nk, calls), (gj, nj, _) = out[True], out[False]
    grad_rel = max(jax.tree_util.tree_leaves(
        jax.tree_util.tree_map(_leaf_rel, gk, gj)))
    unit_rel = {u: float(jnp.max(jnp.abs(nk[u] - nj[u])
                                 / jnp.maximum(jnp.abs(nj[u]), 1e-30)))
                for u in nj}
    norm_rel = max(unit_rel.values())
    log(f"parity: max rel diff clipped grad sum {grad_rel!r}, per-sample "
        f"norms {norm_rel!r} (per clip unit {unit_rel}; tolerance "
        f"{PARITY_TOL})")
    if not (grad_rel <= PARITY_TOL and norm_rel <= PARITY_TOL):
        raise RuntimeError("kernel and jnp gradients differ beyond "
                           f"{PARITY_TOL}")
    if calls == 0:
        raise RuntimeError("the kernel gradient program calls no Pallas "
                           "kernel: it has fallen back to the jnp path")
    return {"grad_rel": grad_rel, "norm_rel": norm_rel}


def phase_mesh(cfg, batch: int, seq: int, steps: int, seed: int,
               chips: int) -> None:
    """The same run on a (chips, 1) mesh and on one device must agree."""
    import numpy as np

    from repro.utils.tree import flatten

    lr = 3e-4
    p_mesh, l_mesh, s_mesh = run_train(cfg, batch, seq, steps, seed,
                                       mesh_data=chips, lr=lr)
    p_one, l_one, s_one = run_train(cfg, batch, seq, steps, seed,
                                    mesh_data=1, lr=lr)
    log(f"mesh: losses on ({chips}, 1) {l_mesh}, on one device {l_one}")
    for s in (s_mesh, s_one):
        if s["steps_done"] != steps:
            raise RuntimeError(f"a run stopped after {s['steps_done']} steps")
    loss_rel = max(abs(a - b) / max(abs(b), 1e-30)
                   for a, b in zip(l_mesh, l_one))
    worst, worst_path = 0.0, ""
    f_mesh, f_one = flatten(p_mesh), flatten(p_one)
    for path, ref in f_one.items():
        ref = np.asarray(ref, np.float32)
        diff = np.max(np.abs(np.asarray(f_mesh[path], np.float32) - ref))
        bound = MESH_PARAM_REL * np.max(np.abs(ref)) + 2 * lr * steps
        if diff / bound > worst:
            worst, worst_path = float(diff / bound), path
    log(f"mesh: max loss rel diff {loss_rel!r} (tolerance {MESH_LOSS_RTOL}); "
        f"worst param diff at {worst:.4f} of its bound in {worst_path} "
        f"(bound {MESH_PARAM_REL} x leaf max + 2 lr steps)")
    if not (loss_rel <= MESH_LOSS_RTOL and worst <= 1.0):
        raise RuntimeError(f"({chips}, 1) mesh and one device disagree")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--steps", type=int, default=3)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=512)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform}); nothing run",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} devices", file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(ROOT, "src"))
    try:
        from repro.launch.train import use_compile_cache
    except ImportError as e:
        print(f"chip_smoke: the repro package is not next to this script "
              f"({e})", file=sys.stderr)
        return 2
    log(f"compile cache: {use_compile_cache() or 'off'}")

    cfg = model_config()
    log(f"device: {dev.platform} {dev.device_kind} x {len(devices)}")
    log(f"model: {ARCH} d_model {cfg.d_model}, heads {cfg.n_heads}/"
        f"{cfg.n_kv_heads} x {cfg.hd}, d_ff {cfg.d_ff}, vocab {cfg.vocab}, "
        f"{cfg.dtype}; reduced: layers {LAYERS} of 28 (AdamW state of the "
        "full depth exceeds one chip's HBM)")
    log(f"run: batch {args.batch} x seq {args.seq}, {args.steps} steps, "
        f"seed {args.seed}")
    if args.chips == 1:
        phases = [("train", phase_train, (cfg, args.batch, args.seq,
                                          args.steps, args.seed)),
                  ("parity", phase_parity, (cfg, args.batch, args.seq,
                                            args.seed))]
    else:
        phases = [("mesh", phase_mesh, (cfg, args.batch, args.seq,
                                        args.steps, args.seed, args.chips))]
    failed = []
    for name, fn, fn_args in phases:
        # every phase runs, so that one call reports all of them
        try:
            fn(*fn_args)
        except Exception as e:  # noqa: BLE001 — reported, then exit non-zero
            log(f"{name}: FAILED: {type(e).__name__}: {e}")
            failed.append(name)
    if failed:
        print(f"chip_smoke: failed phases: {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
