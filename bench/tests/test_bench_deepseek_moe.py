"""The deepseek-moe reference and the expert layer's metrics: the
reference's loss is the program's in f32 at the reference's TINY sizes,
its expert layer held in shares adds up to the whole layer, the float8
control fails the comparison, the FLOP count is pinned, and the readers of
the expert layer's device time attribute by scope (harness/scopes.py)."""
from __future__ import annotations

import json
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import BENCH, ROOT

CONFIG = "deepseek-moe-16b"
CELL = "deepseek-moe-16b.s1024.bk.b8"
STEP = "jit(train_step)"


def _cfg(**over) -> dict:
    from reference import reference_for
    with open(os.path.join(BENCH, "configs", CONFIG + ".json")) as f:
        cfg = json.load(f)
    cfg.update(reference_for(cfg).TINY)
    cfg.update(over)
    return cfg


@pytest.mark.parametrize("T", [16, 24])
def test_row_loss_matches_program_in_f32(T):
    from harness import seeds
    from harness.program import import_program, model_config, unflatten
    from reference import reference_for
    import_program(ROOT)
    from repro.configs.registry import build
    from repro.core.tape import Tape
    cfg = _cfg()
    ref = reference_for(cfg)
    mc = model_config(cfg).with_(dtype="float32", param_dtype="float32",
                                 remat=False, attn_chunk=0)
    flat = seeds.init_flat(11, ref.param_shapes(cfg), jnp.float32)
    # a router ten times the seeded scale, so that top-k picks clearly
    flat = {p: (10 * v if p.endswith("router/w") else v)
            for p, v in flat.items()}
    tokens = jax.random.randint(jax.random.PRNGKey(T), (3, T), 0,
                                cfg["vocab_size"])
    with jax.default_matmul_precision("highest"):
        want = build(mc).apply(unflatten(flat), {"tokens": tokens},
                               Tape.null())
    got = jnp.stack([ref.row_loss(flat, tokens[i], cfg) for i in range(3)])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-6)


@pytest.mark.parametrize("shares", [2, 4, 8])
def test_shares_add_up_to_the_whole_layer(shares):
    """The routed part of the layer held in ``shares`` shares of 64 / shares
    experts, the shared experts counted once: their sum is the layer with
    all 64 held."""
    from reference import deepseek_moe as ref
    s = ref.sizes(_cfg())
    d, ffe, R = s["d"], s["ffe"], s["R"]
    ks = jax.random.split(jax.random.PRNGKey(shares), 5)
    h = jax.random.normal(ks[0], (12, d))
    router = 0.5 * jax.random.normal(ks[1], (d, R))
    up = 0.2 * jax.random.normal(ks[2], (R, d, 2 * ffe))
    down = 0.2 * jax.random.normal(ks[3], (R, ffe, d))
    shared_up = 0.2 * jax.random.normal(ks[4], (d, 2 * s["sff"]))
    shared_down = 0.2 * jax.random.normal(ks[4], (s["sff"], d))
    mm = lambda a, w: jnp.dot(a, w, precision=ref.HIGHEST)
    shared = ref._swiglu(h, shared_up, shared_down, mm)
    whole = shared + ref.routed_part(h, router, up, down, s, range(R), mm)
    n = R // shares
    parts = shared + sum(
        ref.routed_part(h, router, up[i * n:(i + 1) * n],
                        down[i * n:(i + 1) * n], s, range(i * n, (i + 1) * n),
                        mm) for i in range(shares))
    np.testing.assert_allclose(np.asarray(parts), np.asarray(whole),
                               rtol=1e-5, atol=1e-5)


def test_float8_control_fails_in_cell(tiny_cell):
    from test_bench_reference import test_float8_control_fails_the_limits
    test_float8_control_fails_the_limits(
        lambda: tiny_cell(config=CONFIG, traffic="s1024.bk.b8"))


@pytest.mark.parametrize("T,flops", [(1024, 2033319936.0),
                                     (512, 1957822464.0)])
def test_flops_per_token(T, flops):
    """6 N + 12 L H Q T at the configuration's size, with 0.75 expert
    evaluations per expert layer, pinned: the cell's ``mfu`` rests on it."""
    from reference import reference_for
    with open(os.path.join(BENCH, "configs", CONFIG + ".json")) as f:
        cfg = json.load(f)
    assert reference_for(cfg).flops_per_token(cfg, T) == flops


# ------------------------------------------------------------ the readers
def _events():
    """One chip, window [0, 1000): a forward scan ``while`` holding a
    dispatch op, an expert matmul op and a ragged-dot kernel that XLA named;
    the BK norms' loop of the up tap holding another ragged dot; a clipped
    sum of the down tap; an attention op."""
    fwd = f"{STEP}/bk_taps/jvp()/while"
    norms = f"{STEP}/bk_norms/blocks.mlp.experts.up#moe.s/while"
    return [
        (fwd, 0, 300),
        (f"{fwd}/body/moe_dispatch/gather", 10, 40),
        (f"{fwd}/body/moe_experts/jit(silu)/mul", 50, 60),
        ("ragged-dot-none", 100, 180),
        ("ragged-dot-metadata", 190, 200),
        (f"{fwd}/body/attn/dot_general", 200, 260),
        (norms, 300, 500),
        ("ragged-dot-none", 320, 420),
        (f"{STEP}/bk_norms/blocks.attn.o#mm.s/pallas_call", 500, 550),
        (f"{STEP}/bk_clipped_sum/blocks.mlp.experts.down#moe.s/mul",
         600, 640),
    ]


def test_innermost_names_xla_ops_by_the_op_around_them():
    from harness import scopes
    got = scopes.innermost(_events())
    fwd = f"{STEP}/bk_taps/jvp()/while"
    assert got[fwd + "/ragged-dot-none"] == 80
    assert got[fwd + "/ragged-dot-metadata"] == 10
    assert got[f"{STEP}/bk_norms/blocks.mlp.experts.up#moe.s/while"
               "/ragged-dot-none"] == 100
    assert sum(got.values()) == 300 + 250 + 40       # the union, once


def _ctx(monkeypatch, tmp_path, events, steps=2):
    from harness import phases, scopes
    trace = tmp_path / "t.xplane.pb"
    trace.write_bytes(b"")
    host = [("window", 0, 1000)] + [("dispatch", 10 * i, 10 * i + 5)
                                     for i in range(steps)]
    monkeypatch.setattr(phases, "_trace_of", lambda ctx: str(trace))
    monkeypatch.setattr(phases, "load", lambda path: {
        "devices": {0: events}, "host": host})
    scopes._CACHE.clear()
    return types.SimpleNamespace(chips=1, trace={"window_s": 1000e-9})


@pytest.mark.parametrize("metric,ns", [
    ("expert_dispatch_ms", 30),
    ("expert_matmul_ms", 10 + 80 + 10),
    ("expert_norms_ms", 200),
    ("expert_clipped_sum_ms", 40)])
def test_expert_readers(monkeypatch, tmp_path, metric, ns):
    from harness.spec import Cell
    ctx = _ctx(monkeypatch, tmp_path, _events())
    got = Cell(CELL, root=ROOT).reader(metric).read(ctx)
    assert got == pytest.approx(1e3 * ns * 1e-9 / 2)


@pytest.mark.parametrize("metric", ["expert_dispatch_ms", "expert_matmul_ms",
                                    "expert_norms_ms",
                                    "expert_clipped_sum_ms"])
def test_expert_readers_find_nothing_in_a_dense_step(monkeypatch, tmp_path,
                                                     metric):
    """A step without an expert layer (a parent's program, a dense cell):
    the reader returns None and does not raise."""
    from harness.spec import Cell
    dense = [e for e in _events()
             if "moe" not in e[0] and "ragged" not in e[0]]
    ctx = _ctx(monkeypatch, tmp_path, dense)
    assert Cell(CELL, root=ROOT).reader(metric).read(ctx) is None
