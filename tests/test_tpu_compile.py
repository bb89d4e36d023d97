"""Compile the BK step's Pallas kernels, and the expert layer's ragged
dots, for a described TPU v5e.

Interpret mode (the CPU path of every other test) accepts blocks that
Mosaic refuses, so each main-path kernel is compiled here, at qwen2-1.5b
widths (d 1536, qkv 2048, MLP up 17920 / down 8960, vocab 151936; batch 4 x
seq 512, 4 stacked layers), with the blocks `kernels.dispatch` plans for
those shapes, for a v5e chip that is described, not attached. Nothing runs;
a lowering, VMEM or SMEM refusal fails the test. Each kernel's custom call
must carry the kernel's name, which the profiler shows. A small whole BK
step is compiled too, to check how the DP noise is laid out in it.

The topology is described inside a module fixture: only the worker that
runs this file loads the TPU compiler.
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import ghost
from repro.kernels import dispatch
from repro.kernels.clipped_grad import clipped_grad
from repro.kernels.emb_grad import emb_clipped_grad
from repro.kernels.emb_norm import emb_ghost_norm
from repro.kernels.fused_clip import fused_clip_grad
from repro.kernels.ghost_norm import ghost_norm
from repro.kernels.grad_norm_direct import grad_norm_direct

L, B, T, D, V = 4, 4, 512, 1536, 151936
MODE = "bk-mixopt"
BF16, F32, I32 = jnp.bfloat16, jnp.float32, jnp.int32


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _compile(one_chip, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile()


def _kernel(one_chip, name, fn, *shapes):
    """Compile ``fn``, which runs the Pallas kernel ``name``: its custom
    call is named after the kernel, and so is the call's scope in the op
    path (``pallas_call(name=...)``)."""
    compiled = _compile(one_chip, fn, *shapes)
    calls = [line for line in compiled.as_text().splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert calls
    for line in calls:
        assert re.match(rf"\s*(ROOT )?%{name}(\.\d+)? = ", line), line[:80]
        assert re.search(rf'op_name="[^"]*/{name}/pallas_call"', line)
    return compiled


# qwen2-1.5b tap shapes (4 stacked layers): MLP up, LM head, embedding
UP_A, UP_DS = (L, B, T, D), (L, B, T, 17920)
HEAD_A, HEAD_DS = (B, T, D), (B, T, V)
IDS, EMB_DS = (B, T), (B, T, D)


def test_ghost_norm_mlp_up(one_chip):
    plan = dispatch.norm_plan("mm", UP_A, UP_DS, MODE)
    assert (plan.impl, plan.method) == ("kernel", "ghost"), plan
    _kernel(one_chip, "ghost_norm",
            lambda a, g: ghost_norm(a, g, **plan.kwargs()),
            (UP_A, BF16), (UP_DS, BF16))


def test_grad_norm_direct_mlp_up(one_chip):
    plan = dispatch.norm_plan("mm", UP_A, UP_DS, MODE, method="direct")
    assert (plan.impl, plan.method) == ("kernel", "direct"), plan
    _kernel(one_chip, "grad_norm_direct",
            lambda a, g: grad_norm_direct(a, g, **plan.kwargs()),
            (UP_A, BF16), (UP_DS, BF16))


def test_clipped_grad_lm_head(one_chip):
    plan = dispatch.grad_plan("mm", HEAD_A, HEAD_DS)
    assert plan.impl == "kernel", plan
    _kernel(one_chip, "clipped_grad",
            lambda a, c, g: clipped_grad(a, c, g, **plan.kwargs()),
            (HEAD_A, BF16), ((B,), F32), (HEAD_DS, BF16))


def test_fused_clip_layer_scope_adapter(one_chip):
    # the layer-scope kernel holds a whole per-sample grad in VMEM: at these
    # widths that fits a rank-16 adapter on the residual stream, and no
    # dense qwen2 tap (those route to the split path)
    a, ds = (1, B, T, D), (1, B, T, 16)
    plan = dispatch.fused_plan("mm", a, ds, MODE)
    assert (plan.impl, plan.method) == ("kernel", "fused"), plan
    assert dispatch.fused_plan("mm", UP_A, UP_DS, MODE).method == "split"
    _kernel(one_chip, "fused_clip_grad",
            lambda x, g, w: fused_clip_grad(x, g, w, clipping="automatic",
                                            R=1.0, gamma=0.01),
            (a, BF16), (ds, BF16), ((B,), F32))


def test_emb_ghost_norm(one_chip):
    plan = dispatch.norm_plan("emb", IDS, EMB_DS, MODE)
    assert plan.impl == "kernel", plan
    _kernel(one_chip, "emb_ghost_norm",
            lambda i, g: emb_ghost_norm(i, g, **plan.kwargs()),
            (IDS, I32), (EMB_DS, BF16))


def test_emb_clipped_grad(one_chip):
    plan = dispatch.grad_plan("emb", IDS, EMB_DS, vocab=V)
    assert plan.impl == "kernel", plan
    _kernel(one_chip, "emb_clipped_grad",
            lambda i, c, g: emb_clipped_grad(i, c, g, vocab=V,
                                             **plan.kwargs()),
            (IDS, I32), ((B,), F32), (EMB_DS, BF16))


def test_lm_head_ghost_norm_plan(one_chip):
    """p = 151936: no ghost tile fits VMEM, so the plan must be the jnp
    ghost norm (a (B, T, T) Gram in HBM), and that must compile."""
    plan = dispatch.norm_plan("mm", HEAD_A, HEAD_DS, MODE)
    assert (plan.impl, plan.method) == ("jnp", "ghost"), plan
    _compile(one_chip, ghost.sq_norm_mm_ghost,
             (HEAD_A, BF16), (HEAD_DS, BF16))


# the small BK step of bench/tests/record_step_trace.py: qwen2.5-3b's layout
# at d 256, 2 layers, vocab 32768; batch 4 x seq 512; sigma 1, AdamW
SMALL = dict(n_layers=2, d_model=256, d_ff=512, n_heads=2, n_kv_heads=1,
             head_dim=128, vocab=32768)


def test_noise_draw_keeps_each_leafs_shape(one_chip, monkeypatch):
    """The DP noise of every leaf is drawn on the leaf's own shape. A draw
    flattened to rank 1 is a relayout on a TPU (tiled (8, 128) vs linear)
    that XLA does not fuse into the leaf's AdamW update: the compiled step
    would hold a leaf-sized rank-1 counter or normal array between
    separate passes over HBM."""
    from repro.configs.registry import build, get_config
    from repro.core.bk import DPConfig
    from repro.kernels import ops
    from repro.launch.mesh import make_mesh
    from repro.launch.steps import TrainState, make_train_step
    from repro.optim.optimizers import make_optimizer
    from repro.utils.tree import flatten

    monkeypatch.setattr(ops, "_interpret", lambda: False)
    model = build(get_config("qwen2.5-3b").with_(**SMALL))
    mesh = make_mesh((1, 1), ("data", "model"),
                     devices=[next(iter(one_chip.device_set))])
    opt = make_optimizer("adamw", lambda s: jnp.asarray(3e-4, F32))
    params = jax.eval_shape(model.init, jax.ShapeDtypeStruct((2,), jnp.uint32))
    batch = {"tokens": jax.ShapeDtypeStruct((4, 512), I32)}
    dp = DPConfig(mode=MODE, clipping="automatic", R=1.0, gamma=0.01,
                  sigma=1.0)
    step, state_sh, batch_sh = make_train_step(
        model.apply, params, opt, "adamw", dp, 0, mesh, batch)
    state = TrainState(params=params, opt_state=jax.eval_shape(opt.init,
                                                               params),
                       step=jax.ShapeDtypeStruct((), I32),
                       rng=jax.ShapeDtypeStruct((2,), jnp.uint32))

    def placed(tree, shardings):
        return jax.tree_util.tree_map(
            lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
            tree, shardings)

    state, batch = placed(state, state_sh), placed(batch, batch_sh)
    with mesh:
        text = jax.jit(step, in_shardings=(state_sh, batch_sh),
                       out_shardings=(state_sh, None),
                       donate_argnums=(0,)).lower(state, batch).compile() \
            .as_text()

    # element counts of the leaves of rank 2 and up, less the sizes of
    # single dims: a rank-1 array of a dim's size is a broadcast factor
    leaves = flatten(params).values()
    sizes = ({int(np.prod(p.shape)) for p in leaves if p.ndim > 1}
             - {d for p in leaves for d in p.shape})
    assert 32768 * 256 in sizes
    entry = text[text.index("\nENTRY "):]
    entry = entry[:entry.index("\n}")]
    flat = [line.strip()[:120] for line in entry.splitlines()
            if (m := re.match(r"\s*(?:ROOT )?%\S+ = \w+\[(\d+)\]", line))
            and int(m.group(1)) in sizes]
    assert not flat, flat


# deepseek-moe-16b's expert layer on one chip's share: 8 held experts of
# width 1408, 8 rows x 1024 tokens, top 6 -> a buffer of 49152 rows
EM, ED, EF, EH, EB = 8 * 1024 * 6, 2048, 1408, 8, 8


def _ragged_kernels(text):
    return re.findall(r"%ragged-dot-none(?:\.\d+)? = ", text)


def test_grouped_matmul_and_its_input_cotangent(one_chip):
    """The expert layer's matmul and its backward to the rows are each
    the TPU's ragged-dot kernel: no dense product over every expert (a
    ragged dot contracting the weight's last dim lowers to one, the expert
    count times the buffer)."""
    from repro.models.moe import grouped_matmul

    def fwd_bwd(x, w, ct, sizes):
        y, vjp = jax.vjp(lambda x: grouped_matmul(x, w, sizes), x)
        return y, vjp(ct)[0]

    text = _compile(one_chip, fwd_bwd, ((EM, ED), BF16),
                    ((EH, ED, 2 * EF), BF16), ((EM, 2 * EF), BF16),
                    ((EH,), I32)).as_text()
    assert len(_ragged_kernels(text)) == 2
    assert not re.search(rf"= \w+\[{EH},{EM},", text)


def test_grouped_norms_and_clipped_sums(one_chip):
    """BK's expert norms and clipped sums: one ragged-dot kernel each, over
    the (expert, sample) groups and over the experts."""
    def norms_sums(a, tok, ds, gs, C):
        rec = {"a": a, "rows": tok, "gs": gs}
        return (ghost.sq_norm_moe(rec, ds),
                ghost.weighted_grad_moe(rec, C, ds, BF16))

    text = _compile(one_chip, norms_sums, ((5, EB * 1024, ED), BF16),
                    ((5, EM), I32), ((5, EM, 2 * EF), BF16),
                    ((5, EB, EH), I32), ((EB,), F32)).as_text()
    kernels = re.findall(r"= f32\[(\d+),\d+,\d+\][^ ]* custom-call\(", text)
    assert sorted(set(int(k) for k in kernels)) == [EH, EH * EB]
