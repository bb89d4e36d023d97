"""Ghost-norm / direct-norm / weighted-gradient math (pure-jnp reference).

Implements the paper's modules on book-kept tensors:

  module 3  (ghost norm):      ||g_i||_F^2 = < a_i a_i^T , ds_i ds_i^T >_F
  module 4  (direct norm):     instantiate g_i = a_i^T ds_i, take ||.||_F^2
  module 2b' (weighted grad):  G = a^T diag(C) ds   (the BK line 9 einsum)

Layouts (see core.tape):
  mm   a (B,T,d)  ds (B,T,p)      stacked: (L,B,T,d) / (L,B,T,p)
  emb  ids (B,T)  ds (B,T,d)      stacked: (L,B,T)   / (L,B,T,d)
  moe  {'a', 'gs'[, 'rows', 'out', 'w']}    grouped rows of models.moe, +L

All norm accumulation is float32. The fused Pallas kernels in repro.kernels
compute the same quantities without materializing the (T,T) Grams / (d,p)
per-sample grads in HBM; ``use_kernels`` in the engine switches the dispatch.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _f32(x):
    return x.astype(F32)


# Above this many elements for the would-be intermediate (Grams / per-sample
# grads), the norm is computed with a sequential lax.map over (layer, sample)
# so only ONE intermediate is live — the XLA analogue of the fused Pallas
# kernels, and what keeps full-BK book-keeping the only O(model)-sized state.
MAP_THRESHOLD = 1 << 24


def _norm4(a: jnp.ndarray, ds: jnp.ndarray):
    """Canonicalize mm records to (G, B, T, d) with G = stacked layers."""
    if a.ndim == 3:
        return a[None], ds[None]
    if a.ndim == 4:
        return a, ds
    raise ValueError(f"mm record must be 3D or 4D, got {a.shape}")


# =============================================================== matmul (mm)
def sq_norm_mm_ghost(a: jnp.ndarray, ds: jnp.ndarray) -> jnp.ndarray:
    """Ghost norm for s = a W. Returns per-sample squared norms (B,).

    < a_i a_i^T, ds_i ds_i^T >_F, cost 2BT^2(p+d), without forming g_i.
    Large records: per-(layer,sample) lax.map keeps one (T,T) Gram pair live.
    """
    a, ds = _norm4(a, ds)
    G, B, T, _ = a.shape
    # bf16 inputs feed the MXU directly with f32 accumulation — never cast
    # the (large, book-kept) inputs wholesale: XLA hoists such casts out of
    # the lax.map and materializes f32 copies of every tap.
    pe = dict(preferred_element_type=F32)
    if G * B * T * T <= MAP_THRESHOLD:
        ga = jnp.einsum("gbtd,gbsd->gbts", a, a, **pe)
        gg = jnp.einsum("gbtp,gbsp->gbts", ds, ds, **pe)
        return jnp.einsum("gbts,gbts->b", ga, gg, **pe)

    def one(args):
        ab, gb = args
        ga = jnp.einsum("td,sd->ts", ab, ab, **pe)
        gg = jnp.einsum("tp,sp->ts", gb, gb, **pe)
        return jnp.sum(ga * gg)

    n = jax.lax.map(one, (a.reshape((G * B,) + a.shape[2:]),
                          ds.reshape((G * B,) + ds.shape[2:])))
    return n.reshape(G, B).sum(0)


def sq_norm_mm_direct(a: jnp.ndarray, ds: jnp.ndarray) -> jnp.ndarray:
    """Per-sample-grad instantiation norm (Opacus module 4). Cost 2BTpd.
    Large records: per-(layer,sample) lax.map keeps one (d,p) grad live —
    removes Opacus's Bpd space term (mirrors kernels/grad_norm_direct)."""
    a, ds = _norm4(a, ds)
    G, B, _, d = a.shape
    p = ds.shape[-1]
    pe = dict(preferred_element_type=F32)
    if G * B * d * p <= MAP_THRESHOLD:
        g = jnp.einsum("gbtd,gbtp->gbdp", a, ds, **pe)
        return jnp.einsum("gbdp,gbdp->b", g, g)

    def one(args):
        ab, gb = args
        g = jnp.einsum("td,tp->dp", ab, gb, **pe)
        return jnp.sum(g * g)

    n = jax.lax.map(one, (a.reshape((G * B,) + a.shape[2:]),
                          ds.reshape((G * B,) + ds.shape[2:])))
    return n.reshape(G, B).sum(0)


def weighted_grad_mm(a: jnp.ndarray, C: jnp.ndarray, ds: jnp.ndarray,
                     out_dtype=None) -> jnp.ndarray:
    """G = a^T diag(C) ds  -> (d,p) or (L,d,p)."""
    out_dtype = out_dtype or a.dtype
    if a.ndim == 3:
        g = jnp.einsum("btd,b,btp->dp", a, C.astype(a.dtype), ds,
                       preferred_element_type=F32)
    elif a.ndim == 4:
        g = jnp.einsum("lbtd,b,lbtp->ldp", a, C.astype(a.dtype), ds,
                       preferred_element_type=F32)
    else:
        raise ValueError(f"mm record must be 3D or 4D, got {a.shape}")
    return g.astype(out_dtype)


# =========================================================== embedding (emb)
def sq_norm_emb(ids: jnp.ndarray, ds: jnp.ndarray) -> jnp.ndarray:
    """Ghost norm for embedding lookup (Li et al. 2021):
    ||g_i||^2 = sum_{t,t'} 1[id_t == id_t'] (ds_t . ds_t'). Returns (B,).
    Large records: lax.map over samples (one (T,T) pair live)."""
    if ids.ndim == 3:  # (L,B,T) stacked
        L = ids.shape[0]
        return sum(sq_norm_emb(ids[l], ds[l]) for l in range(L))
    B, T = ids.shape
    pe = dict(preferred_element_type=F32)
    if B * T * T <= MAP_THRESHOLD:
        eq = (ids[:, :, None] == ids[:, None, :]).astype(F32)
        gram_g = jnp.einsum("btd,bsd->bts", ds, ds, **pe)
        return jnp.einsum("bts,bts->b", eq, gram_g)

    def one(args):
        ib, gb = args
        eq = (ib[:, None] == ib[None, :]).astype(F32)
        gg = jnp.einsum("td,sd->ts", gb, gb, **pe)
        return jnp.sum(eq * gg)

    return jax.lax.map(one, (ids, ds))


def weighted_grad_emb(ids: jnp.ndarray, C: jnp.ndarray, ds: jnp.ndarray,
                      vocab: int, out_dtype=None) -> jnp.ndarray:
    """G = sum_i C_i sum_t onehot(id_it) ds_it  -> (V,d). Scatter-add."""
    out_dtype = out_dtype or ds.dtype
    if ids.ndim == 3:  # stacked embeddings: ONE segment-sum over all layers,
        # ids offset by l*vocab so each layer scatters into its own row block.
        # Out-of-range ids (pad/sentinel tokens) must keep the per-layer
        # scatter's drop semantics: route them to an OOB flat index instead
        # of letting the offset fold them into the next layer's rows.
        L, d = ids.shape[0], ds.shape[-1]
        w = (_f32(ds) * C[None, :, None, None]).reshape(-1, d)
        off = jnp.arange(L, dtype=ids.dtype)[:, None, None] * vocab
        valid = (ids >= 0) & (ids < vocab)
        flat_ids = jnp.where(valid, ids + off, L * vocab).reshape(-1)
        out = jnp.zeros((L * vocab, d), F32).at[flat_ids].add(w, mode="drop")
        return out.reshape(L, vocab, d).astype(out_dtype)
    w = (_f32(ds) * C[:, None, None]).reshape(-1, ds.shape[-1])
    flat_ids = ids.reshape(-1)
    out = jnp.zeros((vocab, ds.shape[-1]), F32).at[flat_ids].add(w)
    return out.astype(out_dtype)


# ================================================================= MoE (moe)
# Budget, in f32 elements, of the per-(expert, sample) gradients one step of
# ``sq_norm_moe``'s loop holds: the input columns are cut into chunks to fit.
MOE_CHUNK_ELEMS = 1 << 26


def _moe_layers(rec, ds):
    """-> (rec, ds, stacked) with a leading layer axis on every leaf."""
    if rec["gs"].ndim == 3:
        return rec, ds, True
    return ({k: v[None] for k, v in rec.items()}, ds[None], False)


def _layer(tree, l):
    return jax.tree_util.tree_map(
        lambda v: jax.lax.dynamic_index_in_dim(v, l, keepdims=False), tree)


def _a_rows(rec, lo=0, n=None):
    """Columns [lo, lo + n) of the rows the record's matmul read: ``a``
    itself, or the rows of ``a`` that ``rows`` indexes (0 where it points
    past the end)."""
    a = rec["a"]
    if n is not None:
        a = jax.lax.dynamic_slice_in_dim(a, lo, n, 1)
    if "rows" not in rec:
        return a
    return jnp.take(a, rec["rows"], axis=0, mode="fill", fill_value=0)


def _ds_rows(rec, ds, dtype):
    """The cotangent of each row's matmul output: ``ds`` itself, or, where
    the tap sits on the weighted combine of the rows (``out``: the token of
    each row, ``w``: its weight), that token's cotangent times the row's
    weight."""
    if "out" not in rec:
        return ds
    ds = jnp.take(ds.reshape(-1, ds.shape[-1]), rec["out"], axis=0,
                  mode="fill", fill_value=0)
    return (ds * rec["w"][:, None]).astype(dtype)


def _group_grads(a, ds, sizes):
    """a (M,d), ds (M,p), ``sizes`` (G,) consecutive row groups ->
    (G,d,p) f32: sum over each group's rows of a_r^T ds_r. Rows past the
    last group take no part."""
    dn = jax.lax.RaggedDotDimensionNumbers(
        dot_dimension_numbers=(([0], [0]), ([], [])),
        lhs_ragged_dimensions=[0], rhs_group_dimensions=[])
    return jax.lax.ragged_dot_general(a, ds, sizes, dn,
                                      preferred_element_type=F32)


def _col_chunk(d: int, per_col: int) -> int:
    """Largest divisor of d, a multiple of 128 where d has one, whose
    columns times ``per_col`` fit MOE_CHUNK_ELEMS."""
    fits = [c for c in range(1, d + 1)
            if d % c == 0 and c * per_col <= MOE_CHUNK_ELEMS]
    lane = [c for c in fits if c % 128 == 0]
    return max(lane or fits or [1])


def sq_norm_moe(rec: dict, ds: jnp.ndarray) -> jnp.ndarray:
    """Per-sample squared norm of a grouped expert matmul's weight gradient:
    n_b = sum_e || sum_{rows r of (e, b)} a_r^T ds_r ||_F^2 (the experts'
    weights are disjoint parameters).

    The record (models.moe; stacked: a leading layer axis on each leaf)
    holds ``gs`` (B,H), the rows of each (sample, expert) group, the rows
    laid out expert-major; ``a``, the rows the matmul read, or the tokens
    they were gathered from with ``rows`` the token of each row; and, where
    the tap sits on the weighted combine of the matmul's output rows,
    ``out`` and ``w`` (``_ds_rows``). The (expert, sample) gradients come
    from one grouped transposed matmul per layer and column chunk over the
    rows that carry tokens; one chunk is live at a time.
    Beyond-paper extension — the paper never treats MoE."""
    rec, ds, _ = _moe_layers(rec, ds)
    L, d = rec["gs"].shape[0], rec["a"].shape[-1]
    B, H = rec["gs"].shape[1:]
    c = _col_chunk(d, H * B * ds.shape[-1])

    def layer(l):
        r, dl = _layer(rec, l), _layer(ds, l)
        dsr = _ds_rows(r, dl, r["a"].dtype)
        sizes = r["gs"].T.reshape(-1)

        def chunk(j):
            g = _group_grads(_a_rows(r, j * c, c), dsr, sizes)
            return jnp.sum(jnp.square(g), axis=(1, 2)).reshape(H, B).sum(0)

        return jax.lax.map(chunk, jnp.arange(d // c)).sum(0)

    return jax.lax.map(layer, jnp.arange(L)).sum(0)


def row_weights(gs: jnp.ndarray, C: jnp.ndarray, M: int) -> jnp.ndarray:
    """(B,H) group sizes, per-sample C (B,) -> (M,) each row's sample's C;
    0 on rows past the last group."""
    H = gs.shape[1]
    vals = jnp.concatenate([jnp.tile(C.astype(F32), H), jnp.zeros((1,), F32)])
    sizes = jnp.concatenate([gs.T.reshape(-1),
                             (M - jnp.sum(gs)).reshape(1)])
    return jnp.repeat(vals, sizes, total_repeat_length=M)


def weighted_grad_moe(rec: dict, C: jnp.ndarray, ds: jnp.ndarray,
                      out_dtype=None) -> jnp.ndarray:
    """G_e = sum_b C_b sum_{rows r of (e, b)} a_r^T ds_r -> (H,d,p) or
    (L,H,d,p): one grouped transposed matmul per layer over the held
    experts, each row weighted by its sample's clip factor."""
    rec, ds, stacked = _moe_layers(rec, ds)
    out_dtype = out_dtype or rec["a"].dtype

    def layer(l):
        r, dl = _layer(rec, l), _layer(ds, l)
        a = _a_rows(r)
        w = row_weights(r["gs"], C, a.shape[0]).astype(a.dtype)
        return _group_grads(a * w[:, None], _ds_rows(r, dl, a.dtype),
                            jnp.sum(r["gs"], axis=0)).astype(out_dtype)

    g = jax.lax.map(layer, jnp.arange(rec["gs"].shape[0]))
    return g if stacked else g[0]


# ====================================================== hybrid decision rule
def ghost_space(T: int) -> int:
    return 2 * T * T


def direct_space(d: int, p: int) -> int:
    return d * p


def prefer_ghost(T: int, d: int, p: int) -> bool:
    """Paper Sec. 3.2 layerwise rule: ghost norm iff 2 T^2 < p d."""
    return ghost_space(T) < direct_space(d, p)
