"""Substrate tests: optimizers, schedules, accumulation, checkpointing,
fault tolerance, compression, data pipeline."""
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import checkpoint as ckpt
from repro.configs.registry import build, smoke_config
from repro.core.bk import DPConfig, bk_private_grad
from repro.data.pipeline import Pipeline, PipelineConfig
from repro.models.mlp import MLP, MLPConfig
from repro.optim.accumulate import accumulated_private_grad
from repro.optim.optimizers import make_optimizer
from repro.optim.schedules import make_schedule, warmup_cosine
from repro.runtime.compression import dequantize, quantize
from repro.runtime.fault_tolerance import (CheckpointManager, Heartbeat,
                                           PreemptionGuard)
from repro.utils.tree import flatten


def _setup():
    model = MLP(MLPConfig(d_in=8, width=16, depth=2, n_classes=4))
    params = model.init(jax.random.PRNGKey(0))
    batch = {"x": jax.random.normal(jax.random.PRNGKey(1), (8, 8)),
             "y": jax.random.randint(jax.random.PRNGKey(2), (8,), 0, 4)}
    return model, params, batch


# ------------------------------------------------------------------ optimizers
@pytest.mark.parametrize("name", ["sgd", "adamw", "lamb", "adafactor"])
def test_optimizer_reduces_loss(name):
    model, params, batch = _setup()
    opt = make_optimizer(name, lambda s: jnp.asarray(3e-2), weight_decay=0.0)
    state = opt.init(params)
    from repro.core.tape import Tape

    def loss(p):
        return jnp.mean(model.apply(p, batch, Tape(None)))

    l0 = loss(params)
    step_fn = jax.jit(lambda p, s, i: opt.update(jax.grad(loss)(p), s, p, i))
    for i in range(25):
        params, state = step_fn(params, state, jnp.asarray(i))
    assert loss(params) < l0 - 0.05


def test_schedule_shapes():
    fn = warmup_cosine(1e-3, warmup=10, total=100)
    vals = [float(fn(jnp.asarray(s))) for s in [0, 5, 10, 50, 100]]
    assert vals[0] < vals[1] < vals[2]          # warmup ramps
    assert vals[2] >= vals[3] >= vals[4]        # cosine decays
    assert make_schedule("constant", 1.0)(jnp.asarray(7)) == 1.0


# ---------------------------------------------------------------- accumulation
def test_accumulation_matches_full_batch():
    """Microbatched clipped sums + single noise == full-batch BK exactly."""
    model, params, batch = _setup()
    cfg = DPConfig(mode="bk", sigma=0.5)
    rng = jax.random.PRNGKey(9)
    full, _ = jax.jit(lambda p, b, r: bk_private_grad(model.apply, p, b, r, cfg))(
        params, batch, rng)
    acc, _ = jax.jit(lambda p, b, r: accumulated_private_grad(
        model.apply, p, b, r, cfg, microbatch=2))(params, batch, rng)
    for (p, g), (_, r) in zip(sorted(flatten(acc).items()),
                              sorted(flatten(full).items())):
        np.testing.assert_allclose(g, r, rtol=1e-5, atol=1e-6, err_msg=p)


# --------------------------------------------------------------- checkpointing
def test_checkpoint_roundtrip(tmp_path):
    model, params, _ = _setup()
    state = {"params": params, "step": jnp.asarray(7)}
    ckpt.save(str(tmp_path), 7, state)
    restored, step, _ = ckpt.restore(str(tmp_path))
    assert step == 7
    for p, v in flatten(state).items():
        np.testing.assert_array_equal(np.asarray(v), flatten(restored)[p])


def test_checkpoint_keep_k_and_latest(tmp_path):
    model, params, _ = _setup()
    for s in [1, 2, 3, 4, 5]:
        ckpt.save(str(tmp_path), s, {"params": params}, keep=2)
    assert ckpt.steps(str(tmp_path)) == [4, 5]
    assert ckpt.latest_step(str(tmp_path)) == 5


def test_checkpoint_detects_corruption(tmp_path):
    model, params, _ = _setup()
    ckpt.save(str(tmp_path), 1, {"params": params})
    ckpt.save(str(tmp_path), 2, {"params": params})
    # corrupt step 2's payload -> latest valid falls back to step 1
    bad = os.path.join(str(tmp_path), "step_0000000002", "shards.00000.npz")
    with open(bad, "wb") as f:
        f.write(b"garbage")
    assert ckpt.latest_step(str(tmp_path)) == 1


def test_checkpoint_elastic_reshard(tmp_path):
    """Restore applies new shardings (single-device degenerate mesh here,
    exercising the device_put path)."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    model, params, _ = _setup()
    ckpt.save(str(tmp_path), 3, {"params": params})
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    sh = NamedSharding(mesh, P())
    restored, _, _ = ckpt.restore(str(tmp_path), shardings=sh)
    leaf = flatten(restored)["params/l0/w"]
    assert leaf.sharding == sh


# -------------------------------------------------------------- fault tolerance
def test_preemption_guard_and_manager(tmp_path):
    model, params, _ = _setup()
    guard = PreemptionGuard(install=False)
    mgr = CheckpointManager(root=str(tmp_path), every=2, keep=2,
                            async_save=False)
    saved = []
    for step in range(5):
        if mgr.maybe_save(step, {"params": params, "step": jnp.asarray(step)}):
            saved.append(step)
        if step == 3:
            guard.request_stop()
        if guard.should_stop():
            mgr.maybe_save(step, {"params": params, "step": jnp.asarray(step)},
                           force=True)
            break
    state, step, _ = mgr.resume()
    assert step == 3  # the preemption save
    assert saved == [0, 2]


def test_heartbeat_detects_stall():
    stalls = []
    hb = Heartbeat(timeout_s=0.2, on_stall=stalls.append, poll_s=0.05)
    hb.beat(0)
    time.sleep(0.5)
    hb.close()
    assert stalls and stalls[0].last_step == 0
    assert stalls[0].seconds_since_beat > 0.2
    assert "stall" in stalls[0].describe()


# ----------------------------------------------------------------- compression
def test_quantize_unbiased_and_bounded():
    x = jax.random.normal(jax.random.PRNGKey(0), (64, 64)) * 3.0
    qs = [dequantize(*quantize(x, jax.random.PRNGKey(i))) for i in range(30)]
    mean = np.mean([np.asarray(q) for q in qs], axis=0)
    scale = float(jnp.max(jnp.abs(x))) / 127.0
    np.testing.assert_allclose(mean, np.asarray(x), atol=scale)  # unbiased
    q, s = quantize(x, jax.random.PRNGKey(0))
    assert q.dtype == jnp.int8
    np.testing.assert_allclose(dequantize(q, s), np.asarray(x), atol=s + 1e-6)


def test_compressed_allreduce_multidevice_subprocess():
    """Run the pod-axis compressed reduce on 4 virtual devices."""
    import subprocess, sys, textwrap
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import jax, numpy as np, jax.numpy as jnp
        from jax.sharding import Mesh, PartitionSpec as P
        from repro.runtime.compression import compressed_allreduce_mean
        mesh = Mesh(np.array(jax.devices()).reshape(4), ("pod",))
        x = jax.random.normal(jax.random.PRNGKey(0), (4, 128))
        rngs = jax.random.split(jax.random.PRNGKey(1), 4)
        f = jax.shard_map(
            lambda xs, rs: compressed_allreduce_mean(xs[0], rs[0], "pod")[None],
            mesh=mesh, in_specs=(P("pod"), P("pod")), out_specs=P("pod"))
        got = f(x, rngs)
        want = jnp.mean(x, axis=0)
        scale = float(jnp.max(jnp.abs(x))) / 127.0
        for i in range(4):
            np.testing.assert_allclose(got[i], want, atol=2 * scale)
        print("OK")
    """)
    env = dict(os.environ, PYTHONPATH="src")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, cwd=os.path.dirname(os.path.dirname(__file__)),
                       env=env, timeout=300)
    assert "OK" in r.stdout, r.stderr[-2000:]


# ------------------------------------------------------------------- pipeline
def test_pipeline_deterministic_resume():
    cfg = smoke_config("qwen2-1.5b")
    pipe = Pipeline(cfg, PipelineConfig(batch=4, seq_len=8, seed=3))
    b5a = pipe.batch(5)
    b5b = Pipeline(cfg, PipelineConfig(batch=4, seq_len=8, seed=3)).batch(5)
    np.testing.assert_array_equal(b5a["tokens"], b5b["tokens"])
    assert not np.array_equal(pipe.batch(6)["tokens"], b5a["tokens"])


def test_pipeline_poisson_mask():
    cfg = smoke_config("qwen2-1.5b")
    pipe = Pipeline(cfg, PipelineConfig(batch=16, seq_len=8, seed=0,
                                        poisson_q=0.5))
    b = pipe.batch(0)
    assert "mask" in b and b["mask"].shape == b["tokens"].shape
    frac = float(b["mask"][:, 0].mean())
    assert 0.1 < frac < 0.9
