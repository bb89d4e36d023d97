"""The tap mechanism: JAX-native book-keeping + ghost differentiation.

Every generalized-linear op computes ``s = f(a, W) + tap`` where ``tap`` is an
explicit all-zeros argument, and records its activation. The BK engine then
runs one ``jax.vjp`` **with respect to the taps only** — the cotangent of a
tap *is* the output gradient dL/ds of that layer, and because the weights are
not differentiated XLA never builds the non-private parameter-gradient matmul
(module 2b of the paper). This realizes the paper's "ghost differentiation"
and "book-keeping" tricks natively, without PyTorch's requires_grad/origin-
parameter machinery.

Key naming: ``<path>#<kind>[.s]`` where kind is one of
  mm   — matmul: record = activation a, layouts (B,T,d) / stacked (L,B,T,d)
  emb  — embedding lookup: record = int ids (B,T) / (L,B,T)
  moe  — grouped expert matmul: record = {'a': rows (M,d), 'gs': (B,H), ...}
and the ``.s`` suffix marks records stacked over a leading scan (layer) axis.

The parameter owned by a tapped op lives at ``<path>/w`` in the params tree.
All other parameter leaves (biases, norm scales, decay vectors, ...) are
handled by the per-sample-parameter (psp) route in the engine.
"""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp


class Tape:
    """Threads taps into generalized-linear outputs and collects activations.

    A Tape is created inside the traced function. ``taps=None`` runs the model
    untapped (standard training / shape-collection pass); the Tape still
    records ``tap_zeros`` (zeros_like of each tap site output) which under
    ``jax.eval_shape`` yields the tap structure for free.
    """

    def __init__(self, taps: Optional[dict] = None, collect: bool = True):
        self.taps = taps
        self.collect = collect
        self.acts: dict = {}
        self.tap_zeros: dict = {}
        self._prefix: list = []
        self._scan_sub = False       # set by subtape_run: keys are relative
                                     # to the enclosing scan scope

    @classmethod
    def null(cls) -> "Tape":
        """Inference tape: no taps, records nothing (keeps serving HLO free
        of dead tap-zero scan outputs)."""
        return cls(None, collect=False)

    # ------------------------------------------------------------------ scope
    class _Scope:
        def __init__(self, tape, name):
            self.tape, self.name = tape, name

        def __enter__(self):
            self.tape._prefix.append(self.name)

        def __exit__(self, *exc):
            self.tape._prefix.pop()

    def scope(self, name: str) -> "_Scope":
        return Tape._Scope(self, name)

    def key(self, name: str, kind: str) -> str:
        return "/".join(self._prefix + [name]) + "#" + kind

    # ------------------------------------------------------------------- taps
    def _apply_tap(self, key: str, s: jnp.ndarray) -> jnp.ndarray:
        self.tap_zeros[key] = jnp.zeros_like(s)
        # a key absent from a non-None taps dict is a frozen-group op (the
        # policy dropped it from differentiation): pass through untapped
        if self.taps is not None and key in self.taps:
            s = s + self.taps[key]
        return s

    def record(self, name: str, kind: str, s: jnp.ndarray, act) -> jnp.ndarray:
        """Generic tap site: returns s (+tap) and records the activation
        (in the active ``act_storage`` representation — compressing here,
        inside the scan body, is what keeps the stacked ys compact)."""
        if not self.collect:
            return s
        key = self.key(name, kind)
        if key in self.acts:
            raise ValueError(f"duplicate tap key {key!r}")
        s = self._apply_tap(key, s)
        store = _ACT_STORE[-1]
        if not isinstance(store, str):
            # per-tap resolver (scope-relative per-group overrides): sub-
            # Tapes inside scan bodies see only relative keys, so rebuild
            # the merged key from the enclosing scan-scope prefix
            full = key
            if self._scan_sub and _SCOPE_PREFIX:
                full = _SCOPE_PREFIX[-1] + key + ".s"
            store = store(full)
        if store != "native":
            act = store_record(act, store, _ACT_RNG[-1])
        self.acts[key] = act
        return s

    # --------------------------------------------------------- merging (scan)
    def subtaps(self, name: str) -> Optional[dict]:
        """Taps subtree for a scan scope, keys relativized. None if untapped.

        Also pushes the scope's absolute prefix onto the trace-scoped stack
        (popped by the paired :meth:`merge_stacked`) so sub-Tape records —
        which see only relative keys — can resolve their MERGED key for the
        per-tap activation-storage resolver."""
        prefix = "/".join(self._prefix + [name]) + "/"
        _SCOPE_PREFIX.append(prefix)
        if self.taps is None:
            return None
        out = {}
        for k, v in self.taps.items():
            if k.startswith(prefix):
                rel = k[len(prefix):]
                if rel.endswith(".s"):  # stacked marker lives on the merged key
                    rel = rel[:-2]
                out[rel] = v
        return out

    def merge_stacked(self, name: str, acts: dict, tap_zeros: dict) -> None:
        """Merge a scanned sub-tape's stacked outputs under ``name``.

        ``acts``/``tap_zeros`` are the stacked (leading layer axis) trees
        returned as scan ys; keys get prefixed and marked with ``.s``.
        Pops the scope prefix its paired :meth:`subtaps` pushed.
        """
        prefix = "/".join(self._prefix + [name]) + "/"
        if _SCOPE_PREFIX and _SCOPE_PREFIX[-1] == prefix:
            _SCOPE_PREFIX.pop()
        for k, v in acts.items():
            self.acts[prefix + k + ".s"] = v
        for k, v in tap_zeros.items():
            self.tap_zeros[prefix + k + ".s"] = v


def parse_key(key: str):
    """-> (param_path, kind, stacked)."""
    path, _, kindpart = key.rpartition("#")
    stacked = kindpart.endswith(".s")
    kind = kindpart[:-2] if stacked else kindpart
    return path, kind, stacked


def fix_scan_params(tree: dict, tapped: bool) -> dict:
    """Prepare stacked block params for lax.scan under the DP psp route.

    The engine broadcasts every non-ghost leaf to (B, L, ...); scan needs the
    layer axis leading. Ghost weights (leaf key 'w' of tapped ops — the layer
    library's convention) stay (L, ...). No-op when running untapped.
    """
    if not tapped:
        return tree
    from repro.utils.tree import flatten, unflatten  # local: avoid cycle

    flat = {}
    for path, leaf in flatten(tree).items():
        if not path.endswith("/w") and leaf.ndim >= 2:
            leaf = jnp.moveaxis(leaf, 0, 1)
        flat[path] = leaf
    return unflatten(flat)


# ------------------------------------------------------------ tape residency
# Storage policies for book-kept tap records (activations, held cotangents,
# the mixopt per-sample-grad cache) between BK phases 2 and 3:
# (activation storage is applied AT RECORD TIME — inside scan bodies, via
# the ``act_storage`` context — so the stacked native activation ys never
# materialize; post-hoc compression would briefly hold both copies at the
# scan boundary and save nothing at the peak)
#   native     keep the array as produced (bitwise-identical engine output)
#   bf16       hold a bfloat16 copy; fp32 norm/clip accumulation is preserved
#   int8       hold an int8 stochastic-rounding quantization (per-tensor
#              scale, runtime.compression.quantize) — unbiased, loosest parity
#   recompute  hold NOTHING; the cotangent is re-derived in phase 3 by a
#              second chunked backward sweep over the phase-1 linearization
#   auto       per-tap choice by the dispatch residency planner
#              (kernels.dispatch.tape_plan)
# Integer / bool leaves (embedding ids, MoE group sizes) are already compact and
# always pass through untouched.
TAPE_POLICIES = ("native", "bf16", "int8", "recompute", "auto")

# trace-time stacks for the activation-tape storage representation: models
# create sub-Tapes deep inside scan bodies (subtape_run) where keys are
# still scope-relative, so the ACTIVATION side of the residency policy is a
# trace-scoped setting — either a uniform store name, or a RESOLVER
# callable(full_key) -> store that the engine builds from the policy's
# per-group ``tape`` overrides (records inside scan bodies rebuild their
# merged key from the _SCOPE_PREFIX stack pushed by Tape.subtaps).
# ('recompute' keeps acts native — they ARE the standard tape.) int8 uses
# the pushed rng; inside a scan body it is a trace constant, so every layer
# reuses one rounding draw (documented; the held-cotangent side keys
# per-path).
_ACT_STORE: list = ["native"]
_ACT_RNG: list = [None]
_SCOPE_PREFIX: list = []


class act_storage:
    """Context manager scoping the activation-tape storage representation
    around a traced ``apply_fn`` call (engine-internal). ``store`` is a
    store name, or a callable(full_tap_key) -> store name for per-tap
    resolution (the callable must already map recompute/auto to native)."""

    def __init__(self, store, rng=None):
        if isinstance(store, str) and store in ("recompute", "auto"):
            store = "native"
        self.store = store
        self.rng = rng

    def __enter__(self):
        _ACT_STORE.append(self.store)
        _ACT_RNG.append(self.rng)

    def __exit__(self, *exc):
        _ACT_STORE.pop()
        _ACT_RNG.pop()


def store_record(x, policy: str, rng=None):
    """One tap record -> its held representation under a storage policy.

    ``recompute`` never reaches here — dropping the record is the caller's
    move (there is nothing to store). int8 needs ``rng`` for the stochastic
    rounding draw."""
    if policy in ("native", "recompute"):
        return x
    if isinstance(x, dict):          # moe record {'a': float, 'gs': int}
        out = dict(x)
        out["a"] = store_record(x["a"], policy, rng)
        return out
    if not jnp.issubdtype(x.dtype, jnp.floating):
        return x                     # ids / masks: already compact
    if policy == "bf16":
        return x.astype(jnp.bfloat16)
    if policy == "int8":
        from repro.runtime.compression import quantize
        q, scale = quantize(x, rng)
        return {"q": q, "scale": scale}
    raise ValueError(f"unknown tape storage policy {policy!r}; options: "
                     f"{TAPE_POLICIES[:-1]}")


def load_record(stored, dtype=None):
    """Inverse of :func:`store_record`: -> an array in ``dtype`` (the
    record's native dtype) ready for the norm / weighted-grad consumers.
    Loads are elementwise (cast / dequant) so XLA fuses them into the
    consumer — the full-precision copy never materializes in HBM."""
    if isinstance(stored, dict):
        if "q" in stored:            # int8 (q, scale) pair
            from repro.runtime.compression import dequantize
            return dequantize(stored["q"], stored["scale"],
                              dtype or jnp.float32)
        out = dict(stored)
        out["a"] = load_record(stored["a"], dtype)
        return out
    if dtype is not None and stored.dtype != dtype and \
            jnp.issubdtype(stored.dtype, jnp.floating):
        return stored.astype(dtype)
    return stored


def subtape_run(block_fn, params_l, taps_l, *args, collect: bool = True):
    """Helper to run a block inside a scan body with its own sub-Tape.

    Returns (out, (acts, tap_zeros)) so the caller can stack them as scan ys
    and merge with :meth:`Tape.merge_stacked`. With ``collect=False`` the
    aux dicts are empty (inference: no dead tap-zero scan outputs).
    """
    tape = Tape(taps_l, collect=collect)
    tape._scan_sub = True
    out = block_fn(params_l, tape, *args)
    return out, (tape.acts, tape.tap_zeros)
