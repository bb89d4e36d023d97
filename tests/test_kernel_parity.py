"""Kernel-vs-reference parity: every fused Pallas kernel against its pure-jnp
reference (repro.core.ghost), sweeping odd / non-multiple-of-block shapes,
bf16 inputs, and stacked (L,B,T,d) records. Acceptance bar: <= 1e-3 relative
error vs the f32 einsum reference (bf16 inputs get a looser bar — the MXU
accumulates in f32 on both paths but the 8-bit mantissa inputs differ)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ghost
from repro.kernels import dispatch, ops

F32 = jnp.float32
TOL = dict(rtol=1e-3, atol=1e-4)
# the jnp reference casts C to the record dtype (bf16) before the einsum,
# the kernel keeps it f32 — the kernel is the *more* accurate side
TOL_BF16 = dict(rtol=5e-2, atol=2e-2)


def _mk(shape, dtype=F32, seed=0):
    return jax.random.normal(jax.random.PRNGKey(seed), shape,
                             F32).astype(dtype)


def _tol(dtype):
    return TOL if dtype == F32 else TOL_BF16


# odd T / d / p, non-multiples of every block size used below
MM_SHAPES = [
    (1, 2, 7, 5, 9),        # tiny, everything < block
    (1, 3, 33, 17, 23),     # odd, T % bt != 0
    (2, 2, 50, 24, 40),     # stacked, T % bt != 0
    (3, 2, 64, 31, 13),     # stacked, odd d/p
]
DTYPES = [F32, jnp.bfloat16]


@pytest.mark.parametrize("L,B,T,d,p", MM_SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_ghost_norm_mm_parity(L, B, T, d, p, dtype):
    a, ds = _mk((L, B, T, d), dtype), _mk((L, B, T, p), dtype, 1)
    want = ghost.sq_norm_mm_ghost(a, ds)
    got = ops.ghost_norm_mm(a, ds, block_t=16)
    np.testing.assert_allclose(got, want, **_tol(dtype))


@pytest.mark.parametrize("L,B,T,d,p", MM_SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_direct_norm_mm_parity(L, B, T, d, p, dtype):
    a, ds = _mk((L, B, T, d), dtype), _mk((L, B, T, p), dtype, 1)
    want = ghost.sq_norm_mm_direct(a, ds)
    got = ops.direct_norm_mm(a, ds, block_d=16, block_p=16)
    np.testing.assert_allclose(got, want, **_tol(dtype))


@pytest.mark.parametrize("L,B,T,d,p", MM_SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_clipped_grad_mm_parity(L, B, T, d, p, dtype):
    a, ds = _mk((L, B, T, d), dtype), _mk((L, B, T, p), dtype, 1)
    C = jnp.abs(_mk((B,), F32, 2)) + 0.1
    want = ghost.weighted_grad_mm(a, C, ds, F32)
    got = ops.clipped_grad_mm(a, C, ds, block_d=16, block_p=16)
    assert got.shape == (L, d, p)
    np.testing.assert_allclose(got, want, **_tol(dtype))


def test_mm_kernels_unstacked_equal_stacked():
    a, ds = _mk((1, 2, 33, 17)), _mk((1, 2, 33, 23), seed=1)
    C = jnp.abs(_mk((2,), F32, 2)) + 0.1
    np.testing.assert_allclose(ops.ghost_norm_mm(a[0], ds[0], block_t=16),
                               ops.ghost_norm_mm(a, ds, block_t=16), rtol=1e-6)
    np.testing.assert_allclose(
        ops.clipped_grad_mm(a[0], C, ds[0], block_d=16, block_p=16),
        ops.clipped_grad_mm(a, C, ds, block_d=16, block_p=16)[0], rtol=1e-6)


# --------------------------------------------------------------------- emb
EMB_SHAPES = [(1, 2, 9, 6, 11), (2, 3, 33, 16, 50), (3, 2, 50, 24, 37)]


@pytest.mark.parametrize("L,B,T,d,V", EMB_SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_emb_ghost_norm_parity(L, B, T, d, V, dtype):
    ids = jax.random.randint(jax.random.PRNGKey(3), (L, B, T), 0, V)
    ds = _mk((L, B, T, d), dtype, 1)
    want = ghost.sq_norm_emb(ids, ds)
    got = ops.ghost_norm_emb(ids, ds, block_t=16)
    np.testing.assert_allclose(got, want, **_tol(dtype))


@pytest.mark.parametrize("L,B,T,d,V", EMB_SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_emb_clipped_grad_parity(L, B, T, d, V, dtype):
    ids = jax.random.randint(jax.random.PRNGKey(3), (L, B, T), 0, V)
    ds = _mk((L, B, T, d), dtype, 1)
    C = jnp.abs(_mk((B,), F32, 2)) + 0.1
    want = ghost.weighted_grad_emb(ids, C, ds, V, F32)
    got = ops.clipped_grad_emb(ids, C, ds, V, block_v=16)
    assert got.shape == (L, V, d)
    np.testing.assert_allclose(got, want, **_tol(dtype))


def test_emb_grad_oob_ids_dropped_consistently():
    """Out-of-range ids (pad/sentinel) must be DROPPED by both paths — the
    stacked jnp scatter must not fold layer l's OOB id into layer l+1."""
    L, B, T, d, V = 2, 2, 5, 4, 4
    ids = jnp.array([[[0, 4, 1, -1, 2]] * B, [[1, 2, 0, 3, 4]] * B])
    ds = _mk((L, B, T, d), seed=1)
    C = jnp.ones((B,), F32)
    got_jnp = ghost.weighted_grad_emb(ids, C, ds, V, F32)
    got_kern = ops.clipped_grad_emb(ids, C, ds, V, block_v=4)
    # oracle: per-layer scatter of only the in-range rows (note plain
    # .at[].add would WRAP negative ids to the last vocab row — both real
    # paths must drop them instead)
    valid = (ids >= 0) & (ids < V)
    wm = ds * valid[..., None]
    idc = jnp.clip(ids, 0, V - 1)
    want = jnp.stack([
        jnp.zeros((V, d), F32).at[idc[l].reshape(-1)].add(
            wm[l].reshape(-1, d)) for l in range(L)])
    np.testing.assert_allclose(got_jnp, want, **TOL)
    np.testing.assert_allclose(got_kern, want, **TOL)


def test_emb_kernels_unstacked():
    V = 21
    ids = jax.random.randint(jax.random.PRNGKey(3), (2, 17), 0, V)
    ds = _mk((2, 17, 8), seed=1)
    C = jnp.abs(_mk((2,), F32, 2)) + 0.1
    np.testing.assert_allclose(ops.ghost_norm_emb(ids, ds, block_t=8),
                               ghost.sq_norm_emb(ids, ds), **TOL)
    np.testing.assert_allclose(ops.clipped_grad_emb(ids, C, ds, V, block_v=8),
                               ghost.weighted_grad_emb(ids, C, ds, V, F32),
                               **TOL)


# ---------------------------------------------------------------- dispatch
def test_dispatch_prefers_kernels_for_real_shapes():
    for kind, a_shape, ds_shape in [
            ("mm", (2, 8, 128, 64), (2, 8, 128, 64)),
            ("emb", (2, 8, 128), (2, 8, 128, 64))]:
        plan = dispatch.norm_plan(kind, a_shape, ds_shape, "bk")
        assert plan.impl == "kernel", (kind, plan)
        assert plan.method == "ghost"
        gplan = dispatch.grad_plan(kind, a_shape, ds_shape, vocab=512)
        assert gplan.impl == "kernel", (kind, gplan)


def test_dispatch_degenerate_records_stay_jnp():
    # MLP-style T=1 records: the Gram intermediate is one scalar per sample;
    # a kernel launch cannot pay for itself
    plan = dispatch.norm_plan("mm", (8, 1, 16), (8, 1, 16), "bk")
    assert plan.impl == "jnp"


def test_dispatch_blocks_respect_vmem_budget():
    bt = dispatch.block_t_ghost(4096, 4096, 4096)
    assert 4 * (2 * bt * 8192 + 3 * bt * bt) <= dispatch.VMEM_BUDGET
    bd, bp = dispatch.block_dp(4096, 8192, 8192)
    assert 4 * (4096 * (bd + bp) + bd * bp) <= dispatch.VMEM_BUDGET
    bv = dispatch.block_v(1024, 768, 50257)
    assert 4 * (1024 * bv + bv * 768 + 1024 * 768) <= dispatch.VMEM_BUDGET


def test_dispatch_blocks_are_lane_aligned_or_absent():
    # lane dims of a block are multiples of 128 or the whole dim; when no
    # block fits the budget the plan is the jnp path, never an oversize one
    assert dispatch.block_dp(512, 1536, 8960) == (512, 512)
    assert dispatch.block_dp(512, 96, 200) == (96, 200)
    assert dispatch.block_dp(1 << 16, 4096, 4096) is None
    assert dispatch.block_t_ghost(512, 1536, 151936) == 0
    assert dispatch.block_t_ghost(100, 64, 64, lane=True) == 104
    assert dispatch.block_t_ghost(2048, 1536, 1536, lane=True) % 128 == 0
    head = dispatch.norm_plan("mm", (4, 512, 1536), (4, 512, 151936), "bk")
    assert (head.impl, head.method, head.blocks) == ("jnp", "ghost", ())


def test_autotune_raises_for_the_analytic_blocks_only():
    def run(x, block=0):
        if block == 64:
            raise ValueError("refused by the compiler")
        return x * block

    x = jnp.ones(4)
    # a failing non-default candidate is dropped ...
    best = dispatch.autotune(run, [(("block", 64),), (("block", 8),)], x,
                             default=(("block", 8),))
    assert best == (("block", 8),)
    # ... but the analytic choice failing is an error, not a skip
    with pytest.raises(ValueError, match="refused"):
        dispatch.autotune(run, [(("block", 8),)], x,
                          default=(("block", 64),))


def test_dispatch_layerwise_rule_matches_ghost_module():
    # long-T conv-style record -> direct; short-T wide layer -> ghost
    assert dispatch.norm_plan("mm", (4, 4096, 32, 32),
                              (4, 4096, 32, 64), "bk-mixghost").method == "direct"
    assert dispatch.norm_plan("mm", (4, 128, 256, 1024),
                              (4, 128, 256, 1024), "bk-mixghost").method == "ghost"


def test_mixopt_cache_survives_kernel_default():
    """bk-mixopt's phase-3 reuse of instantiated per-sample grads (paper
    Sec 3.3) must still engage with use_kernels=True for small direct-chosen
    records."""
    from repro.core.bk import record_sq_norm
    # direct-favored shape: 2T^2 > pd
    a, ds = _mk((2, 33, 8)), _mk((2, 33, 4), seed=1)
    _, cached = record_sq_norm("x#mm", a, ds, "bk-mixopt", use_kernels=True)
    assert cached is not None and cached.shape == (2, 8, 4)


def test_kernel_report_honors_use_kernels():
    from repro.core.bk import DPConfig
    from repro.core.engine import PrivacyEngine
    from repro.models.mlp import MLP, MLPConfig

    model = MLP(MLPConfig(d_in=8, width=256, depth=1, n_classes=4))
    params = model.init(jax.random.PRNGKey(0))
    batch = {"x": _mk((4, 8)), "y": jnp.zeros((4,), jnp.int32)}
    on = PrivacyEngine(model.apply, DPConfig(use_kernels=True))
    off = PrivacyEngine(model.apply, DPConfig(use_kernels=False))
    rep_on = on.kernel_report(params, batch)
    rep_off = off.kernel_report(params, batch)
    assert any(v["grad"].impl == "kernel" for v in rep_on.values())
    assert all(v["grad"].impl == "jnp" and v["norm"].impl == "jnp"
               for v in rep_off.values())


def test_engine_end_to_end_kernels_vs_jnp():
    """Full BK gradient, kernels on vs off, must agree (transformer smoke
    exercises mm + emb taps; odd seq length)."""
    from dataclasses import replace
    from repro.configs.registry import build, smoke_config
    from repro.core.bk import DPConfig
    from repro.core.engine import make_grad_fn

    from repro.data.synthetic import make_batch

    cfg = smoke_config("qwen2-1.5b").with_(dtype="float32",
                                           param_dtype="float32")
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = make_batch(cfg, B=4, T=13)
    dp = DPConfig(mode="bk", clipping="automatic", use_kernels=True)
    g1, a1 = make_grad_fn(model.apply, dp)(params, batch,
                                           jax.random.PRNGKey(7))
    g0, a0 = make_grad_fn(model.apply, replace(dp, use_kernels=False))(
        params, batch, jax.random.PRNGKey(7))
    np.testing.assert_allclose(a1["per_sample_norms"],
                               a0["per_sample_norms"], rtol=1e-3)
    from repro.utils.tree import flatten
    for (k, v1), (_, v0) in zip(sorted(flatten(g1).items()),
                                sorted(flatten(g0).items())):
        np.testing.assert_allclose(v1, v0, rtol=1e-3, atol=1e-4, err_msg=k)
