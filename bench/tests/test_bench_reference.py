"""The plain reference: its noise is the program's draw by definition, its
model is the program's model in f32, and its float8 control fails the
comparison that sound runs pass."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import ROOT, TINY_LIMITS


def test_noise_is_the_programs_draw():
    from harness.program import import_program
    from reference import noise
    import_program(ROOT)
    from repro.core.noise import _path_rng, counter_normal
    base = jax.random.fold_in(jnp.asarray([3, 2 ** 31 + 9], jnp.uint32), 5)
    for path, shape in (("blocks/mlp/up/w", (2, 8, 24)), ("head/w", (37,))):
        rng = jax.random.fold_in(base, 2)
        want = counter_normal(_path_rng(rng, path), shape)
        got = noise.standard_normal(noise.leaf_key(base, 2, path), shape)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_row_loss_matches_program_in_f32(tiny_cell):
    from harness import seeds
    from harness.program import import_program, model_config, unflatten
    from reference.qwen2 import param_shapes, row_loss
    import_program(ROOT)
    from repro.configs.registry import build
    from repro.core.tape import Tape
    cell = tiny_cell()
    cfg = cell.config
    mc = model_config(cfg).with_(dtype="float32", param_dtype="float32",
                                 remat=False, attn_chunk=0)
    flat = seeds.init_flat(11, param_shapes(cfg), jnp.float32)
    tokens = jax.random.randint(jax.random.PRNGKey(0), (3, 16), 0,
                                cfg["vocab_size"])
    with jax.default_matmul_precision("highest"):
        want = build(mc).apply(unflatten(flat), {"tokens": tokens},
                               Tape.null())
    got = jnp.stack([row_loss(flat, tokens[i], cfg) for i in range(3)])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-6)


@pytest.mark.parametrize("config,traffic", [
    ("qwen2.5-3b", "s1024.bk"), ("qwen2-1.5b", "s512.nonprivate")])
def test_float8_control_fails_in_cell(tiny_cell, config, traffic):
    test_float8_control_fails_the_limits(
        lambda: tiny_cell(config=config, traffic=traffic))


def test_float8_control_fails_the_limits(tiny_cell):
    from harness import check
    from harness.feed import Feed
    from reference.dp_step import DPReference
    cell = tiny_cell()
    cfg, tr = cell.config, cell.traffic
    feed = Feed(21, tr["batch_per_chip"], tr["seq"], cfg["vocab_size"],
                tr["tokens"]["outlier_frac"])
    ctl = DPReference(cfg, tr, "float8").run(21, feed.tokens, 3,
                                              keep_g0=True)
    ref = DPReference(cfg, tr).run(21, feed.tokens, 3,
                                   prog_g0={"control": ctl.pop("g0")})
    values = check.numbers(ctl, ref, "control")
    correct, _ = check.verdict(values, TINY_LIMITS)
    assert not correct, values
