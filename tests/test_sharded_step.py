"""Sharded, donation-clean train step: parity vs single device, shard-local
noise (slice-sized buffers, determinism, variance), donation safety, and the
step-benchmark artifact.

Multi-device tests run in a subprocess (XLA_FLAGS must set the fake device
count before jax's first import), mirroring test_dryrun_small."""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str, ndev: int = 8, timeout: int = 560):
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"))
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={ndev}"
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=timeout, cwd=ROOT)
    assert r.returncode == 0 and "OK" in r.stdout, (r.stdout[-1500:] +
                                                    r.stderr[-3000:])
    return r.stdout


PARITY = textwrap.dedent("""
    import jax, jax.numpy as jnp
    import numpy as np
    from repro.configs.registry import build, smoke_config
    from repro.core.bk import DPConfig
    from repro.data.pipeline import Pipeline, PipelineConfig
    from repro.launch.mesh import make_mesh
    from repro.launch.steps import TrainState, make_train_step
    from repro.optim.optimizers import make_optimizer
    from repro.utils.tree import flatten

    assert len(jax.devices()) == 8
    cfg = smoke_config("qwen2-1.5b").with_(dtype="float32",
                                           param_dtype="float32")
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    pipe = Pipeline(cfg, PipelineConfig(8, 16, seed=0))
    # sigma=0: the full clipping pipeline runs but parity is noise-free
    # (shard-local noise is keyed per shard, so sigma>0 runs are
    # statistically — not bitwise — identical across meshes)
    dp = DPConfig(mode="bk-mixopt", sigma=0.0)

    def run(mesh, microbatch, steps=3):
        opt = make_optimizer("adamw", lambda s: jnp.asarray(1e-3, jnp.float32))
        fn, state_sh, batch_sh = make_train_step(
            model.apply, params, opt, "adamw", dp, microbatch, mesh,
            pipe.batch(0))
        jitted = jax.jit(fn, in_shardings=(state_sh, batch_sh),
                         out_shardings=(state_sh, None), donate_argnums=(0,))
        # device_put to an ALREADY-matching sharding aliases the buffers;
        # copy first so this run's donation cannot delete the shared init
        p0 = jax.tree_util.tree_map(lambda x: jnp.array(x, copy=True), params)
        state = TrainState(params=jax.device_put(p0, state_sh.params),
                           opt_state=jax.device_put(opt.init(p0),
                                                    state_sh.opt_state),
                           step=jnp.asarray(0, jnp.int32),
                           rng=jax.random.PRNGKey(1))
        for step in range(steps):
            batch = jax.device_put(pipe.batch(step), batch_sh)
            state, loss = jitted(state, batch)
        return jax.device_get(state.params), float(loss)

    mesh8 = make_mesh((4, 2), ("data", "model"))
    mesh1 = make_mesh((1, 1), ("data", "model"),
                          devices=jax.devices()[:1])
    for mb in (0, 4):   # full batch AND the microbatch lax.scan path
        p8, l8 = run(mesh8, mb)
        p1, l1 = run(mesh1, mb)
        for k, v in flatten(p1).items():
            # 3 adamw steps amplify cross-shard reduction-order fp noise
            # through the scale-free m/sqrt(v); observed worst ~4e-6 abs
            np.testing.assert_allclose(np.asarray(flatten(p8)[k]),
                                       np.asarray(v), rtol=1e-3, atol=1e-5,
                                       err_msg=f"mb={mb} {k}")
        assert abs(l8 - l1) < 1e-4, (mb, l8, l1)
    print("OK parity")
""")


def test_sharded_step_matches_single_device():
    """Same seed => numerically matching params after N donated steps on a
    (4 data x 2 model) mesh vs a single device, full-batch and microbatched."""
    _run(PARITY)


NOISE_HLO = textwrap.dedent("""
    import re
    import jax, jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core.bk import DPConfig
    from repro.core.policy import as_policy, finalize_noise, resolve_policy
    from repro.launch import sharding as sh
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((4, 2), ("data", "model"))
    # 'head/w' shards ('data','model') -> per-device slice (16, 24)
    params = {"head": {"w": jnp.zeros((64, 48))}}
    pspecs = sh.flat_param_pspecs(params, mesh)
    assert tuple(pspecs["head/w"]) == ("data", "model"), pspecs
    policy = as_policy(DPConfig(mode="bk", sigma=1.0, R=1.0))
    res = resolve_policy(policy, ["head/w"])

    def noised(sums, rng):
        return finalize_noise(policy, res, sums, rng, 1.0, mesh=mesh,
                              pspecs=pspecs)

    ssh = {"head/w": NamedSharding(mesh, pspecs["head/w"])}
    f = jax.jit(noised, in_shardings=(ssh, None))
    sums = jax.device_put({"head/w": jnp.zeros((64, 48))}, ssh)
    rng = jax.random.PRNGKey(3)
    txt = f.lower(sums, rng).compile().as_text()
    # the SPMD-partitioned program must hold ONLY slice-sized f32 buffers:
    # a replicated full-param noise tensor would show up as f32[64,48]
    assert "f32[16,24]" in txt, txt[:2000]
    assert "f32[64,48]" not in txt
    assert "f32[3072]" not in txt  # nor a flattened full-size draw

    # determinism: same (key, mesh) -> bitwise-identical shard-local noise
    n1 = np.asarray(f(sums, rng)["head/w"])
    n2 = np.asarray(f(sums, rng)["head/w"])
    np.testing.assert_array_equal(n1, n2)
    # moments: mean 0, std sigma * S (= 1.0 here) over the full tensor
    assert abs(n1.mean()) < 0.1 and abs(n1.std() - 1.0) < 0.1, \
        (n1.mean(), n1.std())
    # distinct shards draw from distinct fold_in keys
    assert not np.array_equal(n1[:16, :24], n1[16:32, :24])
    print("OK noise hlo")
""")


def test_shard_local_noise_slice_sized_hlo():
    """No replicated full-param noise: every f32 buffer in the lowered
    finalize_noise program is per-device slice-sized; draws are
    deterministic with correct moments and differ across shards."""
    _run(NOISE_HLO)


NOISE_DEVCOUNT = textwrap.dedent("""
    import jax, jax.numpy as jnp
    import numpy as np
    from repro.core.noise import counter_normal, sharded_normal
    from repro.launch.mesh import make_mesh

    rng = jax.random.PRNGKey(5)
    shape = (64, 32)
    from jax.sharding import PartitionSpec as P
    # the counter-based generator is the portable ground truth: every mesh
    # (and the no-mesh path) must reproduce it BITWISE
    ref = np.asarray(counter_normal(rng, shape))
    assert abs(ref.mean()) < 0.1 and abs(ref.std() - 1.0) < 0.1, ref.std()
    for nd in (1, 2, 8):
        mesh = make_mesh((nd, 1), ("data", "model"),
                             devices=jax.devices()[:nd])
        x = np.asarray(sharded_normal(rng, shape, mesh=mesh,
                                      spec=P("data", None)))
        # sigma>0 runs are mesh-PORTABLE: same (key, shape) -> same noise
        # at every device count (not merely statistically matched)
        np.testing.assert_array_equal(x, ref, err_msg=str(nd))
    # sharding BOTH dims on a 2-D mesh still assembles the same tensor
    mesh = make_mesh((4, 2), ("data", "model"))
    x = np.asarray(sharded_normal(rng, shape, mesh=mesh,
                                  spec=P("data", "model")))
    np.testing.assert_array_equal(x, ref)
    # non-divisible dims fall back (same values, GSPMD-partitioned)
    z = sharded_normal(rng, (63, 32), mesh=make_mesh(
        (8, 1), ("data", "model")), spec=P("data", None))
    assert z.shape == (63, 32)
    np.testing.assert_array_equal(np.asarray(z),
                                  np.asarray(counter_normal(rng, (63, 32))))
    print("OK devcounts")
""")


def test_shard_local_noise_bitwise_portable_across_device_counts():
    """Counter-based noise indexed by global coordinates: draws at 1/2/8
    shards (and 2-D meshes) are bitwise identical, so sigma>0 runs are
    mesh-portable; non-divisible dims fall back to the same values."""
    _run(NOISE_DEVCOUNT)


def test_normal_from_bits_is_finite_and_symmetric_at_the_extremes():
    """Every uint32 maps to a finite normal, in order: the top counter cell
    once rounded to u = 1.0 and drew +inf (about 40 times per step on a
    0.65 B param model)."""
    import jax.numpy as jnp
    from jax.scipy.special import ndtri

    from repro.core.noise import normal_from_bits

    bits = jnp.array([0, 255, 256, 0x7FFFFFFF, 0x80000000, 0xFFFFFE00,
                      0xFFFFFF00, 0xFFFFFFFF], jnp.uint32)
    z = np.asarray(normal_from_bits(bits))
    assert np.isfinite(z).all(), z
    assert (np.diff(z) >= 0).all() and z[0] == z[1] < z[2] and z[0] < -5.0
    assert z[-1] == z[-2] == float(ndtri(jnp.float32(1 - 2 ** -24))) > 5.0


def test_counter_normal_wide_counter_consistency():
    """Tensors past 2^32 elements split the counter across both threefry
    words: blocks of a huge virtual tensor agree across decompositions,
    distinct leading blocks differ, and a single dim >= 2^32 raises."""
    import jax
    import pytest as _pytest

    from repro.core.noise import counter_normal

    rng = jax.random.PRNGKey(5)
    full = (1 << 20, 1 << 16)          # 2^36 virtual elements
    a = np.asarray(counter_normal(rng, (2, 4), offsets=(12345, 67),
                                  full_shape=full))
    r0 = np.asarray(counter_normal(rng, (1, 4), offsets=(12345, 67),
                                   full_shape=full))
    r1 = np.asarray(counter_normal(rng, (1, 4), offsets=(12346, 67),
                                   full_shape=full))
    np.testing.assert_array_equal(a[0:1], r0)
    np.testing.assert_array_equal(a[1:2], r1)
    assert not np.array_equal(r0, r1)
    far = np.asarray(counter_normal(rng, (1, 8), offsets=(1 << 19, 0),
                                    full_shape=full))
    assert np.isfinite(far).all() and len(np.unique(far)) > 1
    with _pytest.raises(ValueError, match="2\\^64|2\\^32"):
        counter_normal(rng, (4,), offsets=(0,), full_shape=(1 << 33,))


def _jaxpr_eqns(jaxpr):
    """Every equation of ``jaxpr`` and of the jaxprs nested in it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _jaxpr_eqns(inner)


def _flat_counter_normal(rng, shape, offsets=None, full_shape=None):
    """The noise by its definition, on a flat counter: element i (row-major
    within ``full_shape``) is the inverse normal CDF of the top 24 bits of
    the threefry-2x32 block of (key, (i, 0)), at the centre of its cell,
    the top cell held below 1. Fewer than 2^32 elements."""
    import jax.numpy as jnp
    from jax.extend.random import threefry2x32_p
    from jax.scipy.special import ndtri

    full = full_shape or shape
    offs = offsets or (0,) * len(shape)
    coords = tuple(c + o for c, o in zip(np.indices(shape), offs))
    lo = jnp.asarray(np.ravel_multi_index(coords, full).reshape(-1),
                     jnp.uint32)
    bits, _ = threefry2x32_p.bind(jnp.broadcast_to(rng[0], lo.shape),
                                  jnp.broadcast_to(rng[1], lo.shape),
                                  lo, jnp.zeros_like(lo))
    u = (bits >> jnp.uint32(8)).astype(jnp.float32) * jnp.float32(2 ** -24) \
        + jnp.float32(2 ** -25)
    u = jnp.minimum(u, jnp.float32(1 - 2 ** -24))
    return np.asarray(ndtri(u)).reshape(shape)


@pytest.mark.parametrize("shape", [(48, 40), (3, 20, 36)])
def test_counter_normal_draws_on_the_leafs_shape(shape):
    """The draw is built on the leaf's shape: no reshape anywhere in its
    jaxpr, and the threefry block runs on leaf-shaped operands (on a TPU a
    flattened draw is a relayout that XLA does not fuse into the leaf's
    update). Its values are the flat definition's, element by element,
    with and without shard offsets."""
    import jax

    from repro.core.noise import counter_normal

    rng = jax.random.PRNGKey(11)
    jaxpr = jax.make_jaxpr(lambda r: counter_normal(r, shape))(rng).jaxpr
    eqns = list(_jaxpr_eqns(jaxpr))
    assert not [e for e in eqns if e.primitive.name == "reshape"]
    fry = [e for e in eqns if e.primitive.name == "threefry2x32"]
    assert len(fry) == 1
    assert [tuple(v.aval.shape) for v in fry[0].invars] == [shape] * 4
    assert [tuple(v.aval.shape) for v in fry[0].outvars] == [shape] * 2

    np.testing.assert_array_equal(np.asarray(counter_normal(rng, shape)),
                                  _flat_counter_normal(rng, shape))
    # a device's block of a larger tensor, at its global offsets
    full = tuple(2 * s + 1 for s in shape)
    offs = tuple(range(1, len(shape) + 1))
    np.testing.assert_array_equal(
        np.asarray(counter_normal(rng, shape, offsets=offs, full_shape=full)),
        _flat_counter_normal(rng, shape, offs, full))


PADDED = textwrap.dedent("""
    import jax, jax.numpy as jnp
    import numpy as np
    from repro.configs.registry import build, smoke_config
    from repro.core.bk import DPConfig, bk_private_grad, pad_batch
    from repro.data.pipeline import Pipeline, PipelineConfig
    from repro.launch.mesh import make_mesh
    from repro.utils.tree import flatten

    cfg = smoke_config("qwen2-1.5b").with_(dtype="float32",
                                           param_dtype="float32")
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    # B=6 does NOT divide the 4-way data axis: the engine must pad to 8
    # with masked samples and still take the shard_map'd kernel path
    pipe = Pipeline(cfg, PipelineConfig(6, 16, seed=0))
    batch = pipe.batch(0)
    dp = DPConfig(mode="bk-mixopt", sigma=0.0)
    mesh8 = make_mesh((4, 2), ("data", "model"))
    mesh1 = make_mesh((1, 1), ("data", "model"),
                          devices=jax.devices()[:1])

    padded, mask, Bp = pad_batch(batch, mesh8, 6)
    assert Bp == 8 and mask.shape == (8,), (Bp, mask.shape)
    assert float(mask.sum()) == 6.0
    # the padded shapes divide: the kernel path engages instead of the
    # GSPMD-einsum fallback
    from repro.core.bk import batch_shard
    assert batch_shard(mesh8, Bp) is not None
    assert batch_shard(mesh8, 6) is None

    def grads(mesh):
        with mesh:
            g, aux = jax.jit(
                lambda p, b: bk_private_grad(model.apply, p, b,
                                             jax.random.PRNGKey(7), dp,
                                             mesh=mesh))(params, batch)
        return jax.device_get(g), aux

    g8, aux8 = grads(mesh8)
    g1, aux1 = grads(mesh1)
    # aux reports REAL samples only (pad rows are invisible)
    assert np.asarray(aux8["per_sample_norms"]).shape == (6,)
    np.testing.assert_allclose(np.asarray(aux8["per_sample_norms"]),
                               np.asarray(aux1["per_sample_norms"]),
                               rtol=1e-4, atol=1e-6)
    for k, v in flatten(g1).items():
        np.testing.assert_allclose(np.asarray(flatten(g8)[k]),
                                   np.asarray(v), rtol=1e-3, atol=1e-5,
                                   err_msg=k)
    print("OK padded")
""")


def test_padded_batch_parity_on_mesh():
    """A non-divisible batch (B=6 on a 4-way data axis) is padded with
    masked samples, engages the shard_map'd kernel path, and matches the
    single-device gradients; aux reports real samples only."""
    _run(PADDED)


def test_donated_step_checkpoint_safety(tmp_path):
    """The step donates the whole TrainState; a checkpoint save issued
    right after a step (async writer) must still see valid arrays — the
    copy-before-donate snapshot happens synchronously in maybe_save."""
    import jax
    import jax.numpy as jnp

    from repro.checkpoint import checkpoint as ckpt
    from repro.configs.registry import build, smoke_config
    from repro.core.bk import DPConfig
    from repro.data.pipeline import Pipeline, PipelineConfig
    from repro.launch.mesh import make_train_mesh
    from repro.launch.steps import TrainState, make_train_step
    from repro.optim.optimizers import make_optimizer
    from repro.runtime.fault_tolerance import CheckpointManager
    from repro.utils.tree import flatten

    cfg = smoke_config("qwen2-1.5b").with_(dtype="float32",
                                           param_dtype="float32")
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    opt = make_optimizer("adamw", lambda s: jnp.asarray(1e-3, jnp.float32))
    mesh = make_train_mesh()
    pipe = Pipeline(cfg, PipelineConfig(4, 16, seed=0))
    fn, state_sh, batch_sh = make_train_step(
        model.apply, params, opt, "adamw", DPConfig(mode="bk", sigma=0.1), 0,
        mesh, pipe.batch(0))
    jitted = jax.jit(fn, in_shardings=(state_sh, batch_sh),
                     out_shardings=(state_sh, None), donate_argnums=(0,))
    # commit the initial state to the step's shardings: an uncommitted
    # state would be COPIED to match in_shardings and only the copy donated
    state = TrainState(params=jax.device_put(params, state_sh.params),
                       opt_state=jax.device_put(opt.init(params),
                                                state_sh.opt_state),
                       step=jnp.asarray(0, jnp.int32),
                       rng=jax.random.PRNGKey(1))

    mgr = CheckpointManager(str(tmp_path), every=1, keep=2)
    for step in range(2):
        old = state
        state, loss = jitted(state, jax.device_put(pipe.batch(step),
                                                   batch_sh))
        # donation really happened: the consumed state's buffers are gone
        assert jax.tree_util.tree_leaves(old.params)[0].is_deleted()
        # async save of the NEW state while the next step will donate it
        mgr.maybe_save(step, {"params": state.params,
                              "opt": state.opt_state,
                              "step": np.asarray(step)})
        old = state
    mgr.wait()
    restored, rstep, _ = ckpt.restore(str(tmp_path))
    assert rstep == 1
    live = flatten(jax.device_get(state.params))
    for k, v in flatten(restored["params"]).items():
        assert np.all(np.isfinite(v)), k
        np.testing.assert_array_equal(v, np.asarray(live[k]), err_msg=k)


def test_host_snapshot_copies_out_of_device():
    """ckpt.host_snapshot returns plain numpy even for donated-soon arrays."""
    import jax.numpy as jnp

    from repro.checkpoint.checkpoint import host_snapshot

    snap = host_snapshot({"a": {"w": jnp.ones((3, 3))}, "s": jnp.asarray(4)})
    assert isinstance(snap["a"]["w"], np.ndarray)
    assert snap["s"] == 4


BENCH = os.path.join(ROOT, "BENCH_step.json")


@pytest.mark.skipif(not os.path.exists(BENCH),
                    reason="BENCH_step.json not generated yet "
                           "(benchmarks.step_bench writes it; ci.sh runs it)")
def test_step_bench_artifact_schema():
    """The committed step-level baseline covers >= 2 modes x >= 2 device
    counts with tokens/s and peak-HBM cells."""
    with open(BENCH) as f:
        data = json.load(f)
    cells = data["cells"]
    modes = {c["mode"] for c in cells}
    devs = {c["devices"] for c in cells}
    assert len(modes) >= 2, modes
    assert len(devs) >= 2, devs
    for c in cells:
        assert c["tokens_per_s"] > 0
        assert c["steps_per_s"] > 0
        assert c["peak_hbm_bytes"]["total"] > 0
