"""Kernel dispatch + autotune: the policy layer over the fused Pallas kernels.

Extends the paper's layerwise ghost-vs-direct rule (He et al. 2022;
``ghost.prefer_ghost``) one level down — from *algorithm* choice to *kernel*
choice — per tapped op:

  1. method   ghost vs direct, from the 2T^2 <-> pd space rule (mode 'bk'
              forces ghost, matching the engine's mode semantics);
  2. impl     fused Pallas kernel vs pure-jnp einsum: the kernel's win is
              never materializing the Gram / per-sample-grad intermediate in
              HBM, so records whose intermediate is tiny (fits in registers
              anyway, launch overhead dominates) stay on the jnp path;
  3. blocks   tile sizes chosen so one grid step's operands fit the VMEM
              working-set budget, snapped to the multiples Mosaic accepts
              (8 sublanes, 128 lanes, or the whole dim); a tap for which
              no block fits runs on the jnp path.

Plans are cached per (kind, method, shape, backend). ``autotune`` replaces
the analytic block choice with measured timings on synthetic data (run it
OUTSIDE jit — e.g. from benchmarks/kernel_bench.py or engine warmup — the
measured blocks then win the cache for identical shapes). Environment knobs:

  REPRO_KERNELS=0        force the jnp path everywhere (kill switch)
  REPRO_KERNELS=1        plan the kernel impl even for tiny records (the
                         engine still honors DPConfig.use_kernels=False)
  REPRO_KERNEL_MIN=<n>   impl threshold, in intermediate elements (def. 256)
"""
from __future__ import annotations

import functools
import os
import time
from dataclasses import dataclass

import jax

# f32 bytes one grid step may hold in VMEM (half of ~16 MB/core, leaving the
# other half to Mosaic's double buffering of the next step's blocks)
VMEM_BUDGET = 6 * 2 ** 20

# below this many elements for the avoided intermediate, a fused kernel
# cannot pay for its launch: stay on the (fully XLA-fusable) jnp path
KERNEL_MIN_INTERMEDIATE = 256

_BT_CANDIDATES = (1024, 512, 256, 128, 64, 32, 16, 8)
_BDP_CANDIDATES = (1024, 512, 256, 128)
_BV_CANDIDATES = (4096, 2048, 1024, 512, 256, 128)

_plan_cache: dict = {}


def backend() -> str:
    return jax.default_backend()


def _rup(x: int, m: int) -> int:
    return -(-x // m) * m


@dataclass(frozen=True)
class Plan:
    impl: str        # 'kernel' | 'jnp'
    method: str      # 'ghost' | 'direct' | 'scatter' (emb grad)
    blocks: tuple    # ((name, value), ...) kwargs for the kernels.ops wrapper

    def kwargs(self) -> dict:
        return dict(self.blocks)


# ------------------------------------------------------------- block model
def block_t_ghost(T: int, d: int, p: int, lane: bool = False) -> int:
    """Tile of the packed-triangular ghost-norm grid: 2bt(d+p) operands plus
    3bt^2 live Gram registers per step. ``lane``: the tile is also a lane
    dim (the embedding kernel's row of ids), so below T it must be a
    multiple of 128. 0 when no tile fits (route the tap to jnp)."""
    cap = _rup(min(T, _BT_CANDIDATES[0]), 8)
    for bt in (cap,) + tuple(c for c in _BT_CANDIDATES if c < cap):
        if lane and bt < T and bt % 128:
            continue
        if 4 * (2 * bt * (d + p) + 3 * bt * bt) <= VMEM_BUDGET:
            return bt
    return 0


def block_dp(T: int, d: int, p: int) -> tuple:
    """(bd, bp) for the instantiation-style grids: T(bd+bp) operands plus a
    bd*bp tile per step. Both are lane dims: a multiple of 128, or the
    whole dim when it is at most the largest tile. None when no pair fits
    (route the tap to jnp)."""
    for b in _BDP_CANDIDATES:
        bd = d if d <= b else b
        bp = p if p <= b else b
        if 4 * (T * (bd + bp) + bd * bp) <= VMEM_BUDGET:
            return bd, bp
    return None


def block_v(T: int, d: int, vocab: int) -> int:
    """Vocab tile of the clipped-embedding-grad grid: T*bv one-hot + bv*d
    output tile + T*d cotangents per step. 0 when no tile fits."""
    cap = _rup(min(vocab, _BV_CANDIDATES[0]), 128)
    for bv in _BV_CANDIDATES:
        if bv <= cap and 4 * (T * bv + bv * d + T * d) <= VMEM_BUDGET:
            return bv
    return 0


def _blocked(inter: int, method: str, names: tuple, values) -> Plan:
    """Plan for a kernel that needs blocks: the jnp path when none fit."""
    if not values:
        return Plan("jnp", method, ())
    values = values if isinstance(values, tuple) else (values,)
    return Plan(_impl(inter), method, tuple(zip(names, values)))


# -------------------------------------------------------------- impl model
def _env_state() -> tuple:
    return (os.environ.get("REPRO_KERNELS", ""),
            os.environ.get("REPRO_KERNEL_MIN", ""))


def _impl(intermediate_elems: int) -> str:
    force, min_ = _env_state()
    if force == "0":
        return "jnp"
    if force == "1":
        return "kernel"
    thresh = int(min_) if min_ else KERNEL_MIN_INTERMEDIATE
    return "kernel" if intermediate_elems >= thresh else "jnp"


def _cached(key, mk_plan):
    # env knobs are part of the key so flipping REPRO_KERNELS mid-process
    # invalidates previously planned shapes rather than being ignored
    key = key + _env_state()
    plan = _plan_cache.get(key)
    if plan is None:
        plan = mk_plan()
        _plan_cache[key] = plan
    return plan


# ------------------------------------------------------------------- plans
def norm_plan(kind: str, act_shape, ds_shape, mode: str,
              method: str = "") -> Plan:
    """Per-tap plan for the phase-2 per-sample squared norm.

    ``method`` ('ghost' | 'direct') is the per-ParamGroup override from the
    privacy policy: when set it wins over both the mode-'bk' forced-ghost
    rule and the layerwise 2T^2-vs-pd heuristic."""
    key = ("norm", kind, tuple(act_shape), tuple(ds_shape), mode, method,
           backend())

    def mk():
        if kind == "mm":
            a = act_shape if len(act_shape) == 4 else (1,) + tuple(act_shape)
            L, B, T, d = a
            p = ds_shape[-1]
            from repro.core.ghost import prefer_ghost
            m = method or ("ghost" if mode == "bk" or prefer_ghost(T, d, p)
                           else "direct")
            if m == "ghost":
                return _blocked(L * B * 2 * T * T, m, ("block_t",),
                                block_t_ghost(T, d, p))
            return _blocked(L * B * d * p, m, ("block_d", "block_p"),
                            block_dp(T, d, p))
        if kind == "emb":
            ids = act_shape if len(act_shape) == 3 else (1,) + tuple(act_shape)
            L, B, T = ids
            d = ds_shape[-1]
            # ghost is the only sane norm for embeddings: direct would
            # instantiate (B, V, d); a 'direct' group override is ignored
            return _blocked(L * B * T * T, "ghost", ("block_t",),
                            block_t_ghost(T, d, d, lane=True))
        if kind == "moe":
            a = act_shape if len(act_shape) == 5 else (1,) + tuple(act_shape)
            L, B, E, C, d = a
            p = ds_shape[-1]
            from repro.core.ghost import prefer_ghost
            m = method or ("ghost" if mode == "bk" or prefer_ghost(C, d, p)
                           else "direct")
            if m == "ghost":
                return Plan(_impl(L * B * E * 2 * C * C), m, ())
            return _blocked(L * B * E * d * p, m, ("block_d", "block_p"),
                            block_dp(C, d, p))
        raise ValueError(f"unknown tap kind {kind!r}")

    return _cached(key, mk)


def fused_plan(kind: str, act_shape, ds_shape, mode: str,
               method: str = "") -> Plan:
    """Per-tap plan for a STREAMED single-tap clip unit (scope='layer'):
    phases 2+3 fused at the tap — per-sample norm, clip factor and weighted
    grad in one pass over the cotangent.

    method 'fused'  ONE kernel launch (kernels.fused_clip): per grid step the
                    whole per-sample gradient g_b = a_b^T ds_b lives in VMEM,
                    the norm/clip happen in-register, and C_b * g_b folds into
                    the output accumulator — the contraction runs ONCE (the
                    mixopt trick without the HBM cache). Chosen when the
                    per-sample working set fits the VMEM budget. Not under
                    mode 'bk' (forced-ghost norms) or a 'ghost' group
                    override — those compose the ghost-norm kernel instead.
    method 'split'  compose the existing norm + weighted-grad paths back to
                    back (still streamed: nothing held between them).

    impl 'jnp' on a fused plan is the einsum form of the same single-pass
    contraction (instantiate g once, norm + weight it immediately)."""
    key = ("fused", kind, tuple(act_shape), tuple(ds_shape), mode, method,
           backend())

    def mk():
        if kind != "mm" or mode == "bk" or method == "ghost":
            return Plan("jnp", "split", ())
        a = act_shape if len(act_shape) == 4 else (1,) + tuple(act_shape)
        L, B, T, d = a
        p = ds_shape[-1]
        # per grid step (one sample): a (L,T,d) + ds (L,T,p) operands, the
        # instantiated g (L,d,p) and the (L,d,p) output accumulator
        fits = 4 * (L * T * (d + p) + 2 * L * d * p) <= VMEM_BUDGET
        if not fits:
            return Plan("jnp", "split", ())
        # the avoided intermediate is the second a^T ds contraction's reads
        # plus the held cotangent — same scale as the direct-norm grid
        return Plan(_impl(L * B * d * p), "fused", ())

    return _cached(key, mk)


def grad_plan(kind: str, act_shape, ds_shape, vocab: int = 0) -> Plan:
    """Per-tap plan for the phase-3 clip-weighted gradient (BK line 9)."""
    key = ("grad", kind, tuple(act_shape), tuple(ds_shape), vocab, backend())

    def mk():
        if kind == "mm":
            a = act_shape if len(act_shape) == 4 else (1,) + tuple(act_shape)
            L, B, T, d = a
            p = ds_shape[-1]
            # the kernel fuses diag(C): the avoided HBM intermediate is the
            # (L,B,T,p) weighted cotangent copy
            return _blocked(L * B * T * p, "direct", ("block_d", "block_p"),
                            block_dp(T, d, p))
        if kind == "emb":
            ids = act_shape if len(act_shape) == 3 else (1,) + tuple(act_shape)
            L, B, T = ids
            d = ds_shape[-1]
            return _blocked(L * B * T * d, "scatter", ("block_v",),
                            block_v(T, d, vocab))
        if kind == "moe":
            a = act_shape if len(act_shape) == 5 else (1,) + tuple(act_shape)
            L, B, E, C, d = a
            p = ds_shape[-1]
            return _blocked(L * B * E * C * p, "direct", ("block_d", "block_p"),
                            block_dp(C, d, p))
        raise ValueError(f"unknown tap kind {kind!r}")

    return _cached(key, mk)


# -------------------------------------------------------- residency planner
# The tape residency planner extends the cost model one more level: after
# method (ghost/direct) and impl (kernel/jnp), decide how each tap's
# book-kept state — the held cotangent ds plus the stored activation copy —
# RESIDES between BK phases 2 and 3: stored native, compressed (bf16/int8),
# or not at all (recompute: a second chunked backward sweep re-derives ds in
# phase 3). The analytic rule is bytes-thresholded (compression is ~free,
# recompute costs a partial backward, so small records stay native, mid-size
# records compress, and only records big enough to dominate the book-kept
# footprint pay the re-derivation FLOPs); like the block model it is
# env-tunable, and benchmarks/step_bench.py measures the real per-policy
# peak-HBM/step-time cells the way kernel_bench measures block candidates.
#
#   REPRO_TAPE=<store>            force one store decision everywhere
#   REPRO_TAPE_BF16_MIN=<bytes>   compress records held >= this (def. 64 KiB)
#   REPRO_TAPE_RECOMPUTE_MIN=<b>  re-derive records held >= this (def. 8 MiB)

TAPE_STORES = ("native", "bf16", "int8", "recompute")

TAPE_BF16_MIN = 64 * 2 ** 10
TAPE_RECOMPUTE_MIN = 8 * 2 ** 20


@dataclass(frozen=True)
class TapePlan:
    store: str            # one of TAPE_STORES
    hold_bytes: int       # bytes this tap holds live between phases 2 and 3
    recompute_flops: int  # modeled phase-3 re-derivation cost (paid only
                          # when store == 'recompute')
    itemsize: int = 4     # the cotangent's native dtype width (model dtype)


def _prod(shape) -> int:
    n = 1
    for s in shape:
        n *= int(s)
    return n


def _tape_env() -> tuple:
    return (os.environ.get("REPRO_TAPE", ""),
            os.environ.get("REPRO_TAPE_BF16_MIN", ""),
            os.environ.get("REPRO_TAPE_RECOMPUTE_MIN", ""))


def _hold_bytes(store: str, ds_elems: int, itemsize: int = 4) -> int:
    """Held cotangent bytes between phases (the BK-specific residency; the
    activation copy aliases the standard tape for native/recompute and
    shrinks alongside ds when compressed). ``itemsize`` is the cotangent's
    native dtype width — a bf16 model holds 2 bytes/element natively, so
    the 'bf16' store is a no-op there, never a halving."""
    return {"native": itemsize * ds_elems,
            "bf16": min(2, itemsize) * ds_elems,
            "int8": ds_elems + 4, "recompute": 0, "stream": 0}[store]


def tape_plan(kind: str, act_shape, ds_shape, policy: str = "auto",
              method: str = "", itemsize: int = 4) -> TapePlan:
    """Residency decision for one tap's book-kept state.

    ``policy`` is the resolved request ('auto' lets the byte-threshold rule
    pick; an explicit store pins it but still reports its cost numbers).
    ``itemsize`` is the tap cotangent's dtype width (follows the model
    dtype — the engine threads it from the tap structure so the byte
    thresholds track the real footprint). ``recompute_flops`` models the
    phase-3 re-derivation: one backward from the loss down to this tap's
    site, ~2 * |ds| * d_in FLOPs for the site's own matmul chain."""
    if policy == "stream":
        # engine-assigned (not a user-requestable store): the tap belongs to
        # a streamed single-tap clip unit — phases 2+3 fuse at the tap, the
        # cotangent is consumed the moment it is produced, and NOTHING is
        # held between phases. Zero hold bytes, zero re-derivation, and the
        # REPRO_TAPE force does not apply (there is no record to store).
        return TapePlan("stream", 0, 0, int(itemsize))

    key = ("tape", kind, tuple(act_shape), tuple(ds_shape), policy, method,
           int(itemsize), backend()) + _tape_env()

    def mk():
        ds_elems = _prod(ds_shape)
        d_in = (act_shape[-1] if kind in ("mm", "moe")
                else ds_shape[-1])          # emb: cotangent feature dim
        flops = 2 * ds_elems * int(d_in)
        force, bf16_min, rec_min = _tape_env()
        store = force or policy
        if store == "auto":
            lo = int(bf16_min) if bf16_min else TAPE_BF16_MIN
            hi = int(rec_min) if rec_min else TAPE_RECOMPUTE_MIN
            nat = _hold_bytes("native", ds_elems, itemsize)
            store = ("recompute" if nat >= hi
                     else "bf16" if nat >= lo else "native")
        if store not in TAPE_STORES:
            raise ValueError(f"unknown tape store {store!r}; options: "
                             f"{TAPE_STORES} (or 'auto')")
        return TapePlan(store, _hold_bytes(store, ds_elems, itemsize), flops,
                        int(itemsize))

    return _cached(key, mk)


def fit_tape_budget(plans: dict, budget_bytes: int) -> dict:
    """Upgrade per-tap stores ({key: TapePlan}) biggest-first along
    native -> bf16 -> recompute until the total held bytes fit the budget
    (int8 stays opt-in: its stochastic error is a per-run choice, not a
    planner default). Returns a new {key: TapePlan} dict."""
    order = {"native": "bf16", "bf16": "recompute"}
    out = dict(plans)

    def total() -> int:
        return sum(p.hold_bytes for p in out.values())

    while total() > budget_bytes:
        cands = [(k, p) for k, p in out.items() if p.store in order]
        if not cands:
            break
        k, p = max(cands, key=lambda kp: kp[1].hold_bytes)
        per = {"native": p.itemsize, "bf16": min(2, p.itemsize)}[p.store]
        ds_elems = p.hold_bytes // per
        nxt = order[p.store]
        out[k] = TapePlan(nxt, _hold_bytes(nxt, ds_elems, p.itemsize),
                          p.recompute_flops, p.itemsize)
    return out


# ---------------------------------------------------------------- autotune
def _time(fn, *args, reps: int = 3) -> float:
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps


def autotune(run_fn, candidates, *args, default: tuple) -> tuple:
    """Measure ``run_fn(*args, **dict(cand))`` per candidate block tuple and
    return the fastest. Call OUTSIDE jit with concrete arrays; feed the
    winner back via the plan cache (see ``override_blocks``).

    ``default`` is the analytic plan's blocks: it runs first, and an error
    there (a lowering or compile refusal) propagates — it is what the step
    would run untuned. Any other candidate that fails to lower, compile or
    run is invalid for this shape and is dropped."""
    best = tuple(default)
    best_t = _time(functools.partial(run_fn, **dict(best)), *args)
    for cand in candidates:
        if tuple(cand) == best:
            continue
        try:
            t = _time(functools.partial(run_fn, **dict(cand)), *args)
        except Exception:  # noqa: BLE001 — e.g. a block over the VMEM limit
            continue
        if t < best_t:
            best, best_t = tuple(cand), t
    return best


def override_blocks(key_prefix: str, kind: str, act_shape, ds_shape,
                    blocks: tuple, mode: str = "bk", vocab: int = 0,
                    method: str = "") -> None:
    """Pin measured blocks for one (kind, shape): subsequent plans use them."""
    if key_prefix == "norm":
        plan = norm_plan(kind, act_shape, ds_shape, mode, method)
        key = ("norm", kind, tuple(act_shape), tuple(ds_shape), mode, method,
               backend())
    else:
        plan = grad_plan(kind, act_shape, ds_shape, vocab)
        key = ("grad", kind, tuple(act_shape), tuple(ds_shape), vocab, backend())
    _plan_cache[key + _env_state()] = Plan(plan.impl, plan.method,
                                           tuple(blocks))


def clear_cache() -> None:
    _plan_cache.clear()
