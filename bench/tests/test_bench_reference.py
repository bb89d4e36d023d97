"""The plain reference: its noise is the program's draw by definition, its
model is the program's model in f32, and its float8 control fails the
comparison that sound runs pass. The configuration file's ``reference``
key decides which model the harness compares against."""
from __future__ import annotations

import ast
import importlib.util
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import BENCH, ROOT, TINY_LIMITS


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
    from reference import reference_for
    import_program(ROOT)
    from repro.configs.registry import build
    from repro.core.tape import Tape
    cell = tiny_cell()
    cfg = cell.config
    ref = reference_for(cfg)
    mc = model_config(cfg).with_(dtype="float32", param_dtype="float32",
                                 remat=False, attn_chunk=0)
    flat = seeds.init_flat(11, ref.param_shapes(cfg), jnp.float32)
    tokens = jax.random.randint(jax.random.PRNGKey(0), (3, 16), 0,
                                cfg["vocab_size"])
    with jax.default_matmul_precision("highest"):
        want = build(mc).apply(unflatten(flat), {"tokens": tokens},
                               Tape.null())
    got = jnp.stack([ref.row_loss(flat, tokens[i], cfg) for i in range(3)])
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


def _imports_qwen2(tree) -> bool:
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            if any(a.name == "reference.qwen2" for a in node.names):
                return True
        elif isinstance(node, ast.ImportFrom):
            if node.module == "reference.qwen2" or (
                    node.module == "reference"
                    and any(a.name == "qwen2" for a in node.names)):
                return True
        elif isinstance(node, ast.Constant) and node.value == "reference.qwen2":
            return True
    return False


def test_no_module_names_the_qwen2_reference():
    """Every caller reaches the reference through the configuration's
    ``reference`` key: outside the module itself, nothing under bench/
    imports ``reference.qwen2`` by name."""
    skip = {os.path.join(BENCH, "reference", "qwen2.py"),
            os.path.abspath(__file__)}
    found = []
    for d, _, files in os.walk(BENCH):
        for f in files:
            path = os.path.join(d, f)
            if f.endswith(".py") and path not in skip:
                with open(path) as fh:
                    if _imports_qwen2(ast.parse(fh.read(), path)):
                        found.append(os.path.relpath(path, BENCH))
    assert not found


@pytest.mark.parametrize("name", [None, "qwen9", "../qwen2", "Qwen2",
                                  "noise"])
def test_reference_key_missing_or_unknown(name):
    from reference import reference_for
    with open(os.path.join(BENCH, "configs", "qwen2-1.5b.json")) as f:
        cfg = json.load(f)
    if name is None:
        del cfg["reference"]
    else:
        cfg["reference"] = name
    with pytest.raises(ValueError) as e:
        reference_for(cfg, "bench/configs/qwen2-1.5b.json")
    msg = str(e.value)
    assert "bench/configs/qwen2-1.5b.json" in msg and "'reference'" in msg
    assert "qwen2'" in msg            # the references that exist


@pytest.mark.parametrize("scale", [1.0, 1.001])
def test_planted_reference_decides_correct(tiny_cell, off_chip,
                                           monkeypatch, scale):
    """A copy of qwen2 under another name, which the configuration's
    ``reference`` key points at: the off-chip run compares against it, and
    its loss scaled by 1.001 fails the ``loss`` check."""
    from harness.runner import run_cell
    spec = importlib.util.spec_from_file_location(
        "reference.planted", os.path.join(BENCH, "reference", "qwen2.py"))
    planted = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(planted)
    plain = planted.row_loss
    monkeypatch.setattr(planted, "row_loss",
                        lambda *a, **k: scale * plain(*a, **k))
    monkeypatch.setitem(sys.modules, "reference.planted", planted)
    cell = tiny_cell(reference="planted")
    assert cell.config["reference"] == "planted"
    out = run_cell(cell, seed=2 ** 31 + 5, seconds=0.3, trace=False,
                   t_start=time.perf_counter())
    loss = out["checks"]["loss"]
    if scale == 1.0:
        assert out["correct"], out["checks"]
    else:
        assert out["correct"] is False and loss["value"] > loss["limit"], \
            out["checks"]


@pytest.mark.parametrize("config,T,flops", [
    ("qwen2-1.5b", 1024, 2598764544.0), ("qwen2-1.5b", 512, 2561015808.0),
    ("qwen2.5-3b", 1024, 3817340928.0), ("qwen2.5-3b", 512, 3767009280.0)])
def test_qwen2_flops_per_token(config, T, flops):
    """PaLM's 6 N + 12 L H Q T at the configurations' sizes, pinned: the
    cells' ``mfu`` rests on these counts."""
    from reference import reference_for
    with open(os.path.join(BENCH, "configs", config + ".json")) as f:
        cfg = json.load(f)
    assert reference_for(cfg).flops_per_token(cfg, T) == flops
