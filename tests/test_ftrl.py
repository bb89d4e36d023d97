"""DP-FTRL subsystem + heterogeneous per-group noise tests:

  * FTRL-vs-SGD prefix-sum equivalence at sigma=0
  * tree-aggregation epoch restarts: telescoping, fresh trees, completion
    (honest-restart) variance correction
  * get_mechanism depth pass-through regression (a depth=0 default must not
    clobber the tree's own 30)
  * per-group sigma: noise scales per unit, joint RDP bound vs the flat
    single-sigma bound (equality at scale 1, monotone in the scales)
  * policy-aware plan_cell: the dryrun grid plans the arch's registered
    group-wise policy, not a flat DPConfig
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.accounting import compute_epsilon, effective_sigma
from repro.core.noise import (GaussianMechanism, TreeAggregationMechanism,
                              add_noise, get_mechanism, next_pow2)
from repro.core.policy import (ParamGroup, PrivacyPolicy, finalize_noise,
                               resolve_policy)
from repro.launch.mesh import make_mesh
from repro.optim.optimizers import make_optimizer


# ------------------------------------------------------------------ mechanism
def test_get_mechanism_depth_passthrough():
    """Regression: the former depth=0 default silently built a depth-0 tree
    (prefix_noise over range(0) — NO noise at all)."""
    assert get_mechanism("tree").depth == 30
    assert get_mechanism("tree", depth=0).depth == 30
    assert get_mechanism("tree", depth=7).depth == 7
    # a depth-0 tree would return zeros from prefix_noise — make sure the
    # default actually draws noise
    m = get_mechanism("tree")
    z = m.prefix_noise("p", (8,), 5)
    assert float(jnp.sum(jnp.abs(z))) > 0.0


def test_tree_restart_fresh_epochs_and_telescoping():
    E = 6
    m = TreeAggregationMechanism(seed=3, depth=6, restart_every=E)
    g = {"p": jnp.zeros((16,))}
    acc = jnp.zeros((16,))
    for step in range(E):
        acc = acc + m.add(g, None, 1.0, 1.0, 1.0, step=step)["p"]
    # increments telescope to the epoch-local prefix N_0(E)
    np.testing.assert_allclose(np.asarray(acc),
                               np.asarray(m.prefix_noise("p", (16,), E,
                                                         epoch=0)), rtol=1e-6)
    # first step of epoch 1 is the FRESH tree's N_1(1), not a diff vs epoch 0
    inc = m.add(g, None, 1.0, 1.0, 1.0, step=E)["p"]
    np.testing.assert_allclose(np.asarray(inc),
                               np.asarray(m.prefix_noise("p", (16,), 1,
                                                         epoch=1)), rtol=1e-6)
    # epochs draw independent node noise
    n0 = m.prefix_noise("p", (16,), 1, epoch=0)
    assert float(jnp.max(jnp.abs(n0 - inc))) > 1e-3


def test_tree_completion_variance_correction():
    """With completion the epoch's accumulated noise is the completed
    prefix N(next_pow2(E)) — ONE root-path node (popcount = 1) instead of
    popcount(E) nodes — so the restart rebases on minimum-variance noise."""
    E = 6  # popcount(6) = 2 nodes uncompleted; next_pow2(6) = 8 -> 1 node
    assert next_pow2(E) == 8
    m = TreeAggregationMechanism(seed=0, depth=5, restart_every=E,
                                 completion=True)
    g = {"p": jnp.zeros((4096,))}
    acc = jnp.zeros((4096,))
    for step in range(E):
        acc = acc + m.add(g, None, 1.0, 1.0, 1.0, step=step)["p"]
    want = m.prefix_noise("p", (4096,), 8, epoch=0)
    np.testing.assert_allclose(np.asarray(acc), np.asarray(want),
                               rtol=1e-4, atol=1e-5)
    # single-node variance ~1 (vs popcount(6)=2 without completion)
    v_completed = float(jnp.var(acc))
    m2 = TreeAggregationMechanism(seed=0, depth=5, restart_every=E)
    acc2 = jnp.zeros((4096,))
    for step in range(E):
        acc2 = acc2 + m2.add(g, None, 1.0, 1.0, 1.0, step=step)["p"]
    v_plain = float(jnp.var(acc2))
    assert v_completed == pytest.approx(1.0, rel=0.15)
    assert v_plain == pytest.approx(2.0, rel=0.15)


def test_tree_completion_requires_restarts():
    with pytest.raises(ValueError):
        TreeAggregationMechanism(completion=True)


def test_tree_rejects_steps_past_horizon():
    """Past 2^depth - 1 the prefix collapses (every level index even) and
    increments would SUBTRACT released noise — must raise, not under-noise."""
    m = TreeAggregationMechanism(seed=0, depth=3)
    g = {"p": jnp.zeros((4,))}
    m.add(g, None, 1.0, 1.0, 1.0, step=6)           # t = 7 = horizon: fine
    with pytest.raises(ValueError, match="horizon"):
        m.add(g, None, 1.0, 1.0, 1.0, step=7)       # t = 8 > 2^3 - 1
    with pytest.raises(ValueError, match="horizon"):
        m.add(g, None, 1.0, 1.0, 1.0, step=np.int64(7))  # numpy ints too


def test_train_honors_policy_configured_tree_noise():
    """A policy that already configures tree noise keeps its knobs (no
    silent override); the FTRL anchor restarts at the policy's boundary;
    conflicting boundaries raise."""
    from repro.configs.base import TrainConfig
    from repro.configs.registry import smoke_config
    from repro.launch.train import train

    cfg = smoke_config("qwen2-1.5b").with_(dtype="float32",
                                           param_dtype="float32")
    pol = PrivacyPolicy(groups=(ParamGroup("all", ".*"),), mode="bk",
                        sigma=0.3, noise="tree", noise_depth=4,
                        noise_restart_every=2, noise_completion=True)
    logs = []
    tc = TrainConfig(global_batch=4, seq_len=16, steps=5, lr=1e-3,
                     lr_schedule="constant", optimizer="ftrl")
    _, losses = train(cfg, tc, pol, log=logs.append)
    assert np.all(np.isfinite(losses))
    assert any("restart_every=2" in str(l) and "depth=4" in str(l)
               and "completion=True" in str(l) for l in logs), logs

    import dataclasses
    with pytest.raises(ValueError, match="restart together"):
        train(cfg, dataclasses.replace(tc, restart_every=3), pol,
              log=lambda *a: None)


def test_train_rejects_undersized_tree_depth():
    """Traced steps can't hit the mechanism's concrete-step horizon guard,
    so the driver must validate depth-vs-steps upfront for ANY optimizer."""
    from repro.configs.base import TrainConfig
    from repro.configs.registry import smoke_config
    from repro.launch.train import train

    cfg = smoke_config("qwen2-1.5b").with_(dtype="float32",
                                           param_dtype="float32")
    pol = PrivacyPolicy(groups=(ParamGroup("all", ".*"),), mode="bk",
                        sigma=0.3, noise="tree", noise_depth=3)
    tc = TrainConfig(global_batch=4, seq_len=16, steps=20,
                     optimizer="adamw")
    with pytest.raises(ValueError, match="noise_depth"):
        train(cfg, tc, pol, log=lambda *a: None)


def test_train_rejects_ftrl_knobs_on_other_optimizers():
    from repro.configs.base import TrainConfig
    from repro.configs.registry import smoke_config
    from repro.core.bk import DPConfig
    from repro.launch.train import train

    cfg = smoke_config("qwen2-1.5b").with_(dtype="float32",
                                           param_dtype="float32")
    tc = TrainConfig(global_batch=4, seq_len=16, steps=2,
                     optimizer="adamw", restart_every=10)
    with pytest.raises(ValueError, match="ftrl"):
        train(cfg, tc, DPConfig(mode="bk", sigma=0.1), log=lambda *a: None)


def test_tree_traced_step_matches_python_step():
    m = TreeAggregationMechanism(seed=1, depth=4, restart_every=3,
                                 completion=True)
    g = {"p": jnp.zeros((8,))}
    f = jax.jit(lambda s: m.add(g, None, 1.0, 1.0, 1.0, step=s)["p"])
    for step in range(6):
        np.testing.assert_allclose(
            np.asarray(f(jnp.asarray(step))),
            np.asarray(m.add(g, None, 1.0, 1.0, 1.0, step=step)["p"]),
            rtol=1e-5)


# ----------------------------------------------------------------------- ftrl
def _quad_grads(key, n, d):
    """Deterministic gradient stream for optimizer-only tests."""
    return [jax.random.normal(jax.random.fold_in(key, i), (d,))
            for i in range(n)]


def test_ftrl_sgd_prefix_sum_equivalence():
    """sigma=0, momentum=0, constant lr: theta_t = theta_0 - lr * sum g_s is
    the SGD trajectory exactly (gradients evaluated at the same iterates)."""

    def loss(p, x):
        return jnp.sum((p["w"] @ x - 1.0) ** 2)

    params = {"w": jax.random.normal(jax.random.PRNGKey(0), (4, 4))}
    lr = lambda s: jnp.asarray(0.05, jnp.float32)
    ftrl = make_optimizer("ftrl", lr)
    sgd = make_optimizer("sgd", lr, momentum=0.0)
    pf, sf = params, ftrl.init(params)
    ps, ss = params, sgd.init(params)
    for i in range(7):
        x = jax.random.normal(jax.random.PRNGKey(i + 1), (4,))
        pf, sf = ftrl.update(jax.grad(loss)(pf, x), sf, pf, jnp.asarray(i))
        ps, ss = sgd.update(jax.grad(loss)(ps, x), ss, ps, jnp.asarray(i))
        np.testing.assert_allclose(np.asarray(pf["w"]), np.asarray(ps["w"]),
                                   rtol=1e-5, atol=1e-6)


def test_ftrl_restart_rebases_anchor():
    """After a restart at step E the iterate depends only on gradients seen
    SINCE the restart (prefix sum zeroed, anchor moved)."""
    E, d = 3, 5
    lr = lambda s: jnp.asarray(0.1, jnp.float32)
    opt = make_optimizer("ftrl", lr, restart_every=E)
    params = {"w": jnp.zeros((d,))}
    gs = _quad_grads(jax.random.PRNGKey(2), 2 * E, d)
    p, s = params, opt.init(params)
    for i, g in enumerate(gs):
        p, s = opt.update({"w": g}, s, p, jnp.asarray(i))
        if i == E - 1:
            anchor = p["w"]
    # steps E..2E-1: theta = anchor - lr * sum_{s>=E} g_s
    want = anchor - 0.1 * sum(gs[E:])
    np.testing.assert_allclose(np.asarray(p["w"]), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


def test_ftrl_momentum_matches_reference_recursion():
    beta, lr_v, d = 0.7, 0.05, 4
    opt = make_optimizer("ftrl", lambda s: jnp.asarray(lr_v, jnp.float32),
                         momentum=beta)
    params = {"w": jnp.zeros((d,))}
    gs = _quad_grads(jax.random.PRNGKey(5), 5, d)
    p, s = params, opt.init(params)
    S = jnp.zeros((d,))
    m = jnp.zeros((d,))
    for i, g in enumerate(gs):
        p, s = opt.update({"w": g}, s, p, jnp.asarray(i))
        S = S + g
        m = beta * m + S
        np.testing.assert_allclose(np.asarray(p["w"]),
                                   np.asarray(-lr_v * m),
                                   rtol=1e-5, atol=1e-6)


def test_ftrl_rejects_weight_decay():
    with pytest.raises(ValueError):
        make_optimizer("ftrl", lambda s: 0.1, weight_decay=0.01)


def test_ftrl_end_to_end_tree_noise_restarts():
    """The full train driver: --optimizer ftrl switches the policy to tree
    noise keyed off the optimizer's restart boundary; losses stay finite and
    the run completes across two restarts."""
    from repro.configs.base import TrainConfig
    from repro.configs.registry import smoke_config
    from repro.core.bk import DPConfig
    from repro.launch.train import train

    cfg = smoke_config("qwen2-1.5b").with_(dtype="float32",
                                           param_dtype="float32")
    tc = TrainConfig(global_batch=4, seq_len=16, steps=7, lr=1e-3,
                     lr_schedule="constant", optimizer="ftrl",
                     ftrl_momentum=0.5, restart_every=3,
                     tree_completion=True)
    dp = DPConfig(mode="bk", clipping="automatic", sigma=0.4)
    _, losses = train(cfg, tc, dp, log=lambda *a: None)
    assert len(losses) == 7
    assert np.all(np.isfinite(losses))


# ------------------------------------------------------- heterogeneous noise
def _two_group_policy(scale_a=1.0, scale_b=1.0, sigma=1.2):
    return PrivacyPolicy(groups=(
        ParamGroup("a", "x", R=0.5, scope="group", sigma_scale=scale_a),
        ParamGroup("b", ".*", R=1.0, scope="group", sigma_scale=scale_b),
    ), sigma=sigma)


def test_heterogeneous_epsilon_matches_flat_at_unit_scales():
    res = resolve_policy(_two_group_policy(), ["x/w", "y/w"])
    ms = res.noise_multipliers()
    assert effective_sigma(ms) == pytest.approx(1.2, rel=1e-12)
    e_flat = compute_epsilon(1.2, 0.02, 500, 1e-5)
    e_joint = compute_epsilon(ms, 0.02, 500, 1e-5)
    assert e_joint == pytest.approx(e_flat, rel=1e-9)


def test_heterogeneous_epsilon_monotone_in_scales():
    eps = []
    for s in (0.5, 0.8, 1.0, 1.5, 3.0):
        res = resolve_policy(_two_group_policy(scale_a=s), ["x/w", "y/w"])
        eps.append(compute_epsilon(res.noise_multipliers(), 0.02, 500, 1e-5))
    assert all(a >= b for a, b in zip(eps, eps[1:]))
    # scales >= 1 everywhere -> joint bound <= the flat-sigma bound
    e_flat = compute_epsilon(1.2, 0.02, 500, 1e-5)
    res_up = resolve_policy(_two_group_policy(scale_a=2.0, scale_b=1.0),
                            ["x/w", "y/w"])
    assert compute_epsilon(res_up.noise_multipliers(), 0.02, 500,
                           1e-5) <= e_flat + 1e-9


def test_finalize_noise_per_group_scales():
    """Heterogeneous policies scale each unit's leaves by
    sigma_scale_u * S; homogeneous policies keep the exact pre-existing
    flat draw (same rng path-splits, same std)."""
    pol = _two_group_policy(scale_a=0.25, scale_b=2.0, sigma=0.7)
    res = resolve_policy(pol, ["x/w", "y/w"])
    sums = {"x/w": jnp.zeros((32,)), "y/w": jnp.zeros((32,))}
    rng = jax.random.PRNGKey(9)
    out = finalize_noise(pol, res, sums, rng, 1.0)
    S = res.sensitivity
    ref_a = add_noise({"x/w": sums["x/w"]}, rng, 0.7, 0.25 * S, 1.0)["x/w"]
    ref_b = add_noise({"y/w": sums["y/w"]}, rng, 0.7, 2.0 * S, 1.0)["y/w"]
    np.testing.assert_allclose(np.asarray(out["x/w"]), np.asarray(ref_a))
    np.testing.assert_allclose(np.asarray(out["y/w"]), np.asarray(ref_b))

    # homogeneous: bitwise-identical to the composed-sensitivity float path
    pol0 = _two_group_policy(sigma=0.7)
    res0 = resolve_policy(pol0, ["x/w", "y/w"])
    out0 = finalize_noise(pol0, res0, sums, rng, 1.0)
    ref0 = GaussianMechanism().add(sums, rng, 0.7, res0.sensitivity, 1.0)
    for k in sums:
        np.testing.assert_allclose(np.asarray(out0[k]), np.asarray(ref0[k]))


def test_flat_groups_must_agree_on_sigma_scale():
    pol = PrivacyPolicy(groups=(
        ParamGroup("a", "x", scope="flat", sigma_scale=2.0),
        ParamGroup("b", ".*", scope="flat"),
    ), sigma=1.0)
    with pytest.raises(ValueError, match="sigma_scale"):
        resolve_policy(pol, ["x/w", "y/w"])


def test_sigma_scale_must_be_positive():
    with pytest.raises(ValueError, match="sigma_scale"):
        ParamGroup("a", ".*", sigma_scale=0.0)


def test_policy_restart_knobs_require_tree_noise():
    """Gaussian noise has no tree: restart/completion knobs on a gaussian
    policy would be silently ignored — must raise instead."""
    with pytest.raises(ValueError, match="noise='tree'"):
        PrivacyPolicy(groups=(ParamGroup("all", ".*"),),
                      noise_restart_every=10)
    with pytest.raises(ValueError, match="noise='tree'"):
        PrivacyPolicy(groups=(ParamGroup("all", ".*"),),
                      noise="gaussian", noise_completion=True)
    # tree accepts them
    PrivacyPolicy(groups=(ParamGroup("all", ".*"),), noise="tree",
                  noise_restart_every=10, noise_completion=True)


# ------------------------------------------------------------------ plan_cell
def test_plan_cell_threads_registered_policy(monkeypatch):
    """The dryrun grid plans the arch's registered group-wise policy (and
    its extra per-unit book-keeping) instead of a flat DPConfig."""
    from unittest import mock

    from repro.configs import registry
    from repro.configs.base import SHAPES, ShapeConfig
    from repro.core.bk import DPConfig
    from repro.launch import steps as steps_mod

    small = registry.smoke_config("deepseek-moe-16b").with_(
        name="deepseek-moe-16b", remat=False, attn_chunk=0)
    mesh = make_mesh((1, 1), ("data", "model"))
    with mock.patch.object(steps_mod, "get_config", lambda n: small), \
         mock.patch.dict(SHAPES, {"train_4k": ShapeConfig("train_4k", 16, 8,
                                                          "train")}), \
         mock.patch.dict(steps_mod.TRAIN_MICROBATCH,
                         {"deepseek-moe-16b": 4}):
        plan_pol = steps_mod.plan_cell("deepseek-moe-16b", "train_4k", mesh)
        assert "policy=deepseek-moe-16b(3g)" in plan_pol.note
        plan_flat = steps_mod.plan_cell(
            "deepseek-moe-16b", "train_4k", mesh,
            dp=DPConfig(mode="bk-mixopt", clipping="automatic", sigma=1.0))
        assert "policy=" not in plan_flat.note
        co_pol = plan_pol.lower().compile()
        co_flat = plan_flat.lower().compile()
        ma_pol, ma_flat = co_pol.memory_analysis(), co_flat.memory_analysis()
        assert ma_pol.argument_size_in_bytes == ma_flat.argument_size_in_bytes
        # group-wise clipping runs 3 per-sample norm accumulators + clip
        # factors where flat runs one: the programs must actually differ
        assert co_pol.as_text() != co_flat.as_text()
        assert ma_pol.temp_size_in_bytes > 0
