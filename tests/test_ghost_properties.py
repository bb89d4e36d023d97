"""Property-based tests (hypothesis) for the ghost-norm identities — the
system's core invariants."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.core import ghost

jax.config.update("jax_enable_x64", False)


def arrays(shape, seed):
    rng = np.random.RandomState(seed)
    return jnp.asarray(rng.randn(*shape).astype(np.float32))


@settings(max_examples=25, deadline=None)
@given(B=st.integers(1, 4), T=st.integers(1, 6), d=st.integers(1, 8),
       p=st.integers(1, 8), seed=st.integers(0, 2**16))
def test_ghost_equals_direct_mm(B, T, d, p, seed):
    a = arrays((B, T, d), seed)
    ds = arrays((B, T, p), seed + 1)
    g = np.einsum("btd,btp->bdp", a, ds)
    want = np.sum(g * g, axis=(1, 2))
    np.testing.assert_allclose(ghost.sq_norm_mm_ghost(a, ds), want, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(ghost.sq_norm_mm_direct(a, ds), want, rtol=1e-4, atol=1e-5)


@settings(max_examples=20, deadline=None)
@given(L=st.integers(1, 3), B=st.integers(1, 3), T=st.integers(1, 5),
       d=st.integers(1, 6), p=st.integers(1, 6), seed=st.integers(0, 2**16))
def test_ghost_stacked_sums_over_layers(L, B, T, d, p, seed):
    a = arrays((L, B, T, d), seed)
    ds = arrays((L, B, T, p), seed + 1)
    g = np.einsum("lbtd,lbtp->lbdp", a, ds)
    want = np.sum(g * g, axis=(0, 2, 3))
    np.testing.assert_allclose(ghost.sq_norm_mm_ghost(a, ds), want, rtol=1e-4, atol=1e-5)


@settings(max_examples=20, deadline=None)
@given(B=st.integers(1, 4), T=st.integers(1, 6), V=st.integers(2, 10),
       d=st.integers(1, 6), seed=st.integers(0, 2**16))
def test_embedding_ghost_norm(B, T, V, d, seed):
    rng = np.random.RandomState(seed)
    ids = jnp.asarray(rng.randint(0, V, (B, T)))
    ds = arrays((B, T, d), seed + 1)
    # oracle: scatter into one-hot per-sample grads
    onehot = np.eye(V)[np.asarray(ids)]  # (B,T,V)
    g = np.einsum("btv,btd->bvd", onehot, ds)
    want = np.sum(g * g, axis=(1, 2))
    np.testing.assert_allclose(ghost.sq_norm_emb(ids, ds), want, rtol=1e-4, atol=1e-5)


@settings(max_examples=20, deadline=None)
@given(B=st.integers(1, 3), T=st.integers(1, 5), d=st.integers(1, 6),
       p=st.integers(1, 6), seed=st.integers(0, 2**16))
def test_weighted_grad_mm(B, T, d, p, seed):
    a = arrays((B, T, d), seed)
    ds = arrays((B, T, p), seed + 1)
    C = jnp.abs(arrays((B,), seed + 2)) + 0.1
    want = np.einsum("btd,b,btp->dp", a, C, ds)
    np.testing.assert_allclose(ghost.weighted_grad_mm(a, C, ds), want, rtol=1e-4, atol=1e-4)


@settings(max_examples=15, deadline=None)
@given(B=st.integers(1, 3), H=st.integers(1, 4), d=st.integers(1, 5),
       p=st.integers(1, 5), pad=st.integers(0, 4), seed=st.integers(0, 2**16))
def test_moe_grouped_norm_and_sum(B, H, d, p, pad, seed):
    """Grouped rows (models.moe): norms and clipped sums against the
    per-(expert, sample) gradients made one group at a time."""
    rng = np.random.RandomState(seed)
    gs = rng.randint(0, 4, size=(B, H)).astype(np.int32)
    M = int(gs.sum()) + pad
    a = arrays((M, d), seed)
    ds = arrays((M, p), seed + 1)
    C = jnp.abs(arrays((B,), seed + 2)) + 0.1
    an, dn = np.asarray(a), np.asarray(ds)
    want_n, want_g, r = np.zeros(B), np.zeros((H, d, p)), 0
    for e in range(H):
        for b in range(B):
            g = an[r:r + gs[b, e]].T @ dn[r:r + gs[b, e]]
            want_n[b] += np.sum(g * g)
            want_g[e] += np.asarray(C)[b] * g
            r += gs[b, e]
    rec = {"a": a, "gs": jnp.asarray(gs)}
    np.testing.assert_allclose(ghost.sq_norm_moe(rec, ds), want_n,
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(ghost.weighted_grad_moe(rec, C, ds), want_g,
                               rtol=1e-4, atol=1e-5)


def test_hybrid_rule_matches_paper_examples():
    # Paper Sec 3.1: ImageNet conv1 of VGG11: 2T^2 = 5e9 >> pd = 1.7e3 -> direct
    assert not ghost.prefer_ghost(T=224 * 224, d=27, p=64)
    # RoBERTa: T=256, layer ~1-4M params -> ghost
    assert ghost.prefer_ghost(T=256, d=1024, p=1024)
