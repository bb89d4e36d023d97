"""End-to-end behaviour tests: full train driver with checkpoint/restart
determinism, serve driver, engine facade, HLO analyzer, sharding rules."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import TrainConfig
from repro.configs.registry import build, smoke_config
from repro.core.bk import DPConfig
from repro.launch.mesh import make_mesh
from repro.launch.train import train


def _smoke_cfg():
    return smoke_config("qwen2-1.5b").with_(dtype="float32",
                                            param_dtype="float32")


def test_train_loop_end_to_end(tmp_path):
    """Loss decreases under DP training; checkpoints are written."""
    tc = TrainConfig(global_batch=8, microbatch=4, seq_len=16, steps=12,
                     lr=2e-3, checkpoint_dir=str(tmp_path),
                     checkpoint_every=5)
    dp = DPConfig(mode="bk-mixopt", clipping="automatic", sigma=0.3)
    params, losses = train(_smoke_cfg(), tc, dp, log=lambda *a: None)
    assert len(losses) == 12
    assert np.mean(losses[-3:]) < np.mean(losses[:3])
    from repro.checkpoint import checkpoint as ckpt
    assert ckpt.latest_step(str(tmp_path)) is not None


def test_train_resume_exact(tmp_path):
    """train(12) == train(7) + resume(5) bit-exactly (fault tolerance)."""
    dp = DPConfig(mode="bk", clipping="automatic", sigma=0.2)
    tc_full = TrainConfig(global_batch=4, seq_len=16, steps=10, lr=1e-3,
                          lr_schedule="constant")
    p_full, _ = train(_smoke_cfg(), tc_full, dp, log=lambda *a: None)

    tc_a = TrainConfig(global_batch=4, seq_len=16, steps=6, lr=1e-3,
                       lr_schedule="constant",
                       checkpoint_dir=str(tmp_path), checkpoint_every=1)
    train(_smoke_cfg(), tc_a, dp, log=lambda *a: None)
    tc_b = TrainConfig(global_batch=4, seq_len=16, steps=10, lr=1e-3,
                       lr_schedule="constant",
                       checkpoint_dir=str(tmp_path), checkpoint_every=100)
    p_resumed, _ = train(_smoke_cfg(), tc_b, dp, log=lambda *a: None)

    from repro.utils.tree import flatten
    for k, v in flatten(p_full).items():
        np.testing.assert_allclose(np.asarray(v),
                                   np.asarray(flatten(p_resumed)[k]),
                                   rtol=1e-6, atol=1e-7, err_msg=k)


def test_generate_roundtrip():
    cfg = _smoke_cfg()
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    from repro.launch.serve import generate
    prompts = jax.random.randint(jax.random.PRNGKey(1), (2, 6), 0, cfg.vocab)
    out = generate(model, params, prompts, gen_len=4)
    assert out.shape == (2, 10)
    np.testing.assert_array_equal(np.asarray(out[:, :6]), np.asarray(prompts))


def test_hlo_analyzer_trip_counts():
    from repro.utils.hlo import analyze_hlo, xla_cost_analysis
    D, L = 64, 8

    def f(params, x0):
        def body(h, wl):
            return jnp.tanh(h @ wl), None
        h, _ = jax.lax.scan(body, x0, params)
        return h

    co = jax.jit(f).lower(jax.ShapeDtypeStruct((L, D, D), jnp.float32),
                          jax.ShapeDtypeStruct((D, D), jnp.float32)).compile()
    t = analyze_hlo(co.as_text())
    assert abs(t["flops"] - 2 * D**3 * L) / (2 * D**3 * L) < 1e-6
    # XLA's own analysis undercounts by the trip count
    assert xla_cost_analysis(co)["flops"] < t["flops"]


def test_xla_cost_analysis_normalizes_both_shapes():
    """cost_analysis() returns a dict on older jax, [dict] on newer — the
    helper must take both (and tolerate empties)."""
    from repro.utils.hlo import xla_cost_analysis

    class Dict:
        def cost_analysis(self):
            return {"flops": 7.0}

    class List:
        def cost_analysis(self):
            return [{"flops": 7.0}]

    class Empty:
        def cost_analysis(self):
            return []

    assert xla_cost_analysis(Dict()) == {"flops": 7.0}
    assert xla_cost_analysis(List()) == {"flops": 7.0}
    assert xla_cost_analysis(Empty()) == {}


def test_sharding_rules_sanitize():
    import numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.launch.sharding import sanitize, spec_for
    mesh = make_mesh((1, 1), ("data", "model"))

    class FakeMesh:
        shape = {"data": 16, "model": 16, "pod": 2}
    assert sanitize(P("data", "model"), (32, 32001), FakeMesh()) == P("data", None)
    assert sanitize(P(("pod", "data"),), (1,), FakeMesh()) == P(None)
    assert sanitize(P(None, "model"), (77, 64), FakeMesh()) == P(None, "model")
    assert spec_for("blocks/attn/qkv/w", 3) == P(None, "data", "model")
    assert spec_for("blocks/ln1/g", 2) == P()
    assert spec_for("embed/w", 2) == P(None, "model")


def test_engine_rejects_unknown_mode():
    from repro.core.engine import make_grad_fn
    with pytest.raises(ValueError):
        make_grad_fn(lambda *a: None, DPConfig(mode="nope"))
