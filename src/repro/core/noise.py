"""DP noise mechanisms (pluggable via PrivacyPolicy.noise).

``add_noise`` draws per-leaf Gaussian noise with a path-stable RNG split so
the noise is reproducible per parameter regardless of tree iteration order.

A ``NoiseMechanism`` is any object with

    add(flat_grads, rng, sigma, sensitivity, denom, step=None) -> dict

plus the restart hooks

    state_dict() -> dict      # everything a privacy-exact restart needs
    load_state(state) -> None # restore/validate; raise ValueError on drift

State dicts are persisted inside every checkpoint (``checkpoint.run_state``)
and replayed at resume BEFORE the first restored step runs. Both mechanisms
here are counter-based — their noise at step t is a pure function of
(seed, path, t) — so their restorable state is exactly their configuration,
and ``load_state`` is a drift guard: resuming with a different node seed,
restart period or completion flag would silently put the run on a fresh
noise path (re-drawing noise the adversary has already seen answered
differently — a privacy violation, not just a reproducibility bug), so it
raises instead. A future *stateful* mechanism (e.g. banded matrix
factorization holding an O(band) buffer) returns its buffers as numpy
arrays inside ``state_dict``; the RunState packer stores array-valued
entries in the sliced checkpoint payload and round-trips them bitwise.

returning ``(G + sigma * scale * xi) / denom`` per leaf, where ``scale`` is
either one L2 sensitivity shared by every leaf (a bare R for flat clipping,
the policy's composed sensitivity for group-wise clipping) or a
``{path: scale}`` mapping for heterogeneous per-group noise
(``ParamGroup.sigma_scale``; the accounting composes the per-group Gaussian
curves jointly — see ``accounting.compute_epsilon``). Two are registered:

  'gaussian'  the classic Gaussian mechanism (per-step independent noise)
  'tree'      binary-tree aggregation (Kairouz et al. 2021, DP-FTRL): the
              CUMULATIVE noise over steps 1..t is the sum of the O(log t)
              tree-node noises covering [1..t]; ``add`` injects the per-step
              increment N(t) - N(t-1) so the optimizer's running gradient
              sum carries exactly N(t). Node noise is keyed by a fixed seed
              (NOT the per-step rng) so node draws are shared across steps
              and the increments telescope.

Tree restarts (DP-FTRL epoch restarts): with ``restart_every=E`` the tree is
rebuilt every E steps — epoch e = step // E gets its own node seeds and the
local prefix index resets to 1, matching an FTRL optimizer that rebases
theta0 and zeroes its gradient prefix at the same boundary (``optim.ftrl``).
With ``completion=True`` (the honest-restart variance correction, Honaker
completion as in the DP-FTRL reference code) the LAST increment of each
epoch advances the prefix to the next power of two, so the noise baked into
the restart point is the completed tree's root path — popcount(2^k) = 1 node
of variance instead of popcount(E) — at no extra privacy cost (every tree
node is already released).

``partial_sigma`` implements the distributed-noise trick: on an n-way data
axis each shard adds N(0, (sigma/sqrt(n))^2) *before* the gradient
all-reduce; the reduced sum then carries exactly N(0, sigma^2) — identical
privacy, no single-host noise-generation bottleneck. (Used by the launcher
when ``dp.distributed_noise`` is on.)

Shard-local generation: ``sharded_normal`` draws each param's noise under a
mesh so every device generates ONLY its NamedSharding slice — no replicated
full-parameter noise tensor ever exists in HBM (the dominant phase-4
allocation for large models). Generation is COUNTER-BASED
(``counter_normal``): the value at a tensor's global coordinate is a pure
function of (key, global linear index) via threefry-2x32 + the inverse
normal CDF, so the same (seed, shape) produces BITWISE-identical noise on
1 device, 8 devices, or any mesh shape — sigma>0 runs are mesh-portable,
not just statistically matched (previously draws were keyed per
(shard index, mesh) and only sigma=0 runs were portable).
"""
from __future__ import annotations

import zlib
from typing import Mapping

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P


def _path_rng(rng, path: str):
    return jax.random.fold_in(rng, zlib.crc32(path.encode()) & 0x7FFFFFFF)


def _spec_axis_names(entry):
    """PartitionSpec entry -> tuple of mesh axis names (may be nested)."""
    if entry is None:
        return ()
    if isinstance(entry, (tuple, list)):
        return tuple(entry)
    return (entry,)


def _raw_key(rng):
    """PRNGKey -> raw uint32[2] key data (typed new-style keys included)."""
    if jnp.issubdtype(rng.dtype, jax.dtypes.prng_key):
        return jax.random.key_data(rng)
    return rng


def counter_normal(rng, shape, dtype=jnp.float32, offsets=None,
                   full_shape=None):
    """Counter-based N(0,1): the value at global coordinate x is a pure
    function of (key, linear index of x within ``full_shape``) — one
    threefry-2x32 block per element with the index as the counter (the raw
    block primitive: the high-level hashes pair positions across the array,
    making values length-dependent), then :func:`normal_from_bits`. A
    device holding only the local block passes its per-dim global
    ``offsets``; any partition of the same
    (key, full_shape) reproduces bitwise the same global tensor.

    Tensors past 2^32 elements split the counter across BOTH threefry
    words: the trailing dims that fit a uint32 ride word 0 (so tensors
    under 2^32 keep their exact pre-split draws), the leading-block index
    rides word 1.

    The counter planes, the threefry block and the normal all keep the
    leaf's own ``shape``. On a TPU a rank-2 array is tiled (8, 128) and a
    rank-1 one is not, so flattening would be a physical relayout, which
    keeps XLA from fusing the draw into the leaf's optimizer update and
    costs separate passes over HBM. Elementwise on the leaf's shape, the
    whole draw fuses into that update."""
    from jax.extend.random import threefry2x32_p
    full = tuple(full_shape) if full_shape is not None else tuple(shape)
    # split point: dims [k:] index counter word 0 exactly; dims [:k] word 1
    k, trail = len(full), 1
    while k > 0 and trail * int(full[k - 1]) < (1 << 32):
        k -= 1
        trail *= int(full[k])
    lead = 1
    for s in full[:k]:
        lead *= int(s)
    if lead >= 1 << 32:
        raise ValueError(
            f"counter_normal supports < 2^64 elements per tensor (and no "
            f"single dim >= 2^32), got shape {full}")

    def plane(dims) -> jnp.ndarray:
        idx = jnp.zeros(shape, jnp.uint32)
        stride = 1
        for d in reversed(dims):
            coord = jax.lax.broadcasted_iota(jnp.uint32, shape, d)
            if offsets is not None:
                coord = coord + jnp.uint32(offsets[d])
            idx = idx + coord * jnp.uint32(stride)
            stride *= int(full[d])
        return idx

    key = _raw_key(rng)
    lo, hi = plane(range(k, len(full))), plane(range(k))
    bits, _ = threefry2x32_p.bind(jnp.broadcast_to(key[0], lo.shape),
                                  jnp.broadcast_to(key[1], lo.shape),
                                  lo, hi)
    return normal_from_bits(bits, dtype)


def normal_from_bits(bits, dtype=jnp.float32):
    """uint32 random bits -> N(0,1): the top 24 bits to a (0, 1) uniform,
    then the inverse normal CDF. In f32 the top cell's 1 - 2^-25 rounds to
    1.0, where the inverse is +inf (once in 2^24 elements, so dozens of
    times per step on a model of 10^9 params); it is held at the largest
    f32 below 1 instead, and every other draw is unchanged."""
    from jax.scipy.special import ndtri
    u = (bits >> jnp.uint32(8)).astype(jnp.float32) * jnp.float32(2 ** -24) \
        + jnp.float32(2 ** -25)
    u = jnp.minimum(u, jnp.float32(1 - 2 ** -24))
    return ndtri(u).astype(dtype)


def sharded_normal(rng, shape, dtype=jnp.float32, mesh=None, spec=None):
    """N(0,1) draw where each device generates only its shard, bitwise
    IDENTICAL across device counts and mesh shapes.

    ``spec`` is the leaf's PartitionSpec on ``mesh``. The draw runs inside a
    shard_map: every shard computes its global per-dim offsets from its axis
    indices and generates its local block with :func:`counter_normal`, so
    the per-device noise buffer is slice-sized while the assembled logical
    tensor equals the unsharded draw exactly (ROADMAP PR-4 follow-up: noise
    is now indexed by global coordinates, not by (shard, mesh)). Mesh axes
    the spec does not mention produce identical blocks, so the output is
    genuinely replicated across them. Falls back to the unsharded
    counter-based draw (same values, GSPMD-partitioned) when there is no
    mesh, the spec is trivial, or a sharded dim does not divide."""
    if mesh is None or spec is None:
        return counter_normal(rng, shape, dtype)
    tail = tuple(spec) + (None,) * (len(shape) - len(tuple(spec)))
    names = [n for e in tail for n in _spec_axis_names(e)]
    if not names or all(mesh.shape[n] == 1 for n in names):
        return counter_normal(rng, shape, dtype)
    local_shape = []
    for dim, entry in zip(shape, tail):
        n = 1
        for a in _spec_axis_names(entry):
            n *= mesh.shape[a]
        if dim % n:
            return counter_normal(rng, shape, dtype)  # non-divisible
        local_shape.append(dim // n)
    local_shape = tuple(local_shape)

    def draw(key):
        offs = []
        for dim, loc, entry in zip(shape, local_shape, tail):
            idx = jnp.uint32(0)
            for a in _spec_axis_names(entry):
                idx = idx * jnp.uint32(mesh.shape[a]) \
                    + jnp.uint32(jax.lax.axis_index(a))
            offs.append(idx * jnp.uint32(loc))
        return counter_normal(key, local_shape, dtype, offsets=offs,
                              full_shape=shape)

    return jax.shard_map(draw, mesh=mesh, in_specs=P(),
                         out_specs=P(*tail), check_vma=False)(rng)


def _scale_for(sensitivity, path: str) -> float:
    """Per-leaf noise scale: a shared float or a {path: scale} mapping."""
    if isinstance(sensitivity, Mapping):
        return sensitivity[path]
    return sensitivity


def next_pow2(n: int) -> int:
    """Smallest power of two >= n (tree-completion horizon)."""
    return 1 << max(0, int(n) - 1).bit_length()


def _spec_of(pspecs, path: str):
    """Per-leaf PartitionSpec lookup (None mesh/pspecs -> replicated draw)."""
    if pspecs is None:
        return None
    return pspecs.get(path)


def add_noise(flat_grads: dict, rng, sigma: float, R, denom: float,
              mesh=None, pspecs=None) -> dict:
    """(G + sigma*R*xi) / denom per leaf. sigma==0 -> just G/denom.
    ``R`` may be a float (shared scale) or a {path: scale} mapping; with
    ``mesh``/``pspecs`` each device draws only its slice of xi."""
    out = {}
    for path, g in flat_grads.items():
        if sigma > 0.0:
            xi = sharded_normal(_path_rng(rng, path), g.shape, jnp.float32,
                                mesh=mesh, spec=_spec_of(pspecs, path))
            g = g + (sigma * _scale_for(R, path)) * xi.astype(g.dtype)
        out[path] = g / denom
    return out


def partial_sigma(sigma: float, n_shards: int) -> float:
    return sigma / (n_shards ** 0.5)


# ----------------------------------------------------------------- mechanisms
class GaussianMechanism:
    """Per-step independent Gaussian noise — the DP-SGD default."""
    name = "gaussian"

    def __init__(self, seed: int = 0, depth: int = 0,
                 restart_every: int = 0, completion: bool = False):
        del seed, depth, restart_every, completion  # stateless: per-step rng

    def state_dict(self) -> dict:
        """Per-step noise is keyed off the step rng the TrainState already
        persists — the mechanism itself carries no restorable state."""
        return {"name": self.name}

    def load_state(self, state: dict) -> None:
        if state.get("name") != self.name:
            raise ValueError(
                f"checkpoint noise state is {state.get('name')!r} but the "
                f"resumed run configures {self.name!r} — resuming would "
                "switch the noise mechanism mid-release")

    def add_leaf(self, path: str, g, rng, sigma: float, scale,
                 denom: float, step=None, mesh=None, spec=None):
        """One leaf of ``add`` — the fused noise+optimizer path consumes
        leaves one at a time so the full noised-gradient tree is never
        live."""
        del step  # per-step independence: the per-call rng is the state
        if sigma > 0.0:
            xi = sharded_normal(_path_rng(rng, path), g.shape, jnp.float32,
                                mesh=mesh, spec=spec)
            g = g + (sigma * scale) * xi.astype(g.dtype)
        return g / denom

    def add(self, flat_grads: dict, rng, sigma: float, sensitivity,
            denom: float, step=None, mesh=None, pspecs=None) -> dict:
        return {path: self.add_leaf(path, g, rng, sigma,
                                    _scale_for(sensitivity, path), denom,
                                    step=step, mesh=mesh,
                                    spec=_spec_of(pspecs, path))
                for path, g in flat_grads.items()}


class TreeAggregationMechanism:
    """Binary-tree aggregated noise (DP-FTRL).

    Node (level l, index i>=1) covers steps [(i-1)*2^l + 1, i*2^l]. At step t
    (1-indexed) the prefix [1..t] is covered by one node per set bit b of t,
    with index i = t >> b — so the cumulative noise N(t) sums popcount(t)
    unit-variance node draws, giving per-coordinate variance
    popcount(t) * (sigma * sensitivity)^2 <= (log2(t)+1) * (sigma * S)^2
    instead of the t * (sigma * S)^2 of per-step independent noise on a
    released prefix sum.

    The per-call ``rng`` is IGNORED: node noises must be identical whenever
    the same node covers different prefixes, so they key off the fixed
    ``seed`` + (path, epoch, level, index) only. ``step`` may be a python int
    or a traced jnp scalar (node/epoch indices are data to ``fold_in``).

    ``restart_every=E`` rebuilds the tree every E steps (epoch restarts):
    step t maps to epoch e = step//E with local prefix index (step % E) + 1,
    and every epoch draws from fresh node seeds. An FTRL optimizer zeroes its
    gradient prefix at the same boundary, so the first increment of a new
    epoch is the full N_e(1) of the fresh tree. ``completion=True``
    additionally advances the LAST increment of each epoch to
    N_e(next_pow2(E)), so the model state that the restart rebases on
    carries single-root-node noise variance (the honest-restart correction);
    it is a no-op when E is a power of two.

    Cost note: with a traced step every level draws a full leaf-sized normal
    (the dead levels' zero weights can't be DCE'd), i.e. 2*depth draws per
    leaf per ``add``. ``depth`` only needs to cover the horizon
    (2^depth - 1 steps; next_pow2(E) under restarts) — set
    ``PrivacyPolicy.noise_depth`` to ceil(log2(steps + 1)) to pay only what
    the run needs.
    """
    name = "tree"

    def __init__(self, seed: int = 0, depth: int = 30,
                 restart_every: int = 0, completion: bool = False):
        self.seed = seed
        self.depth = depth           # supports up to 2^depth - 1 steps
        self.restart_every = int(restart_every)
        self.completion = bool(completion)
        if self.completion and self.restart_every <= 0:
            raise ValueError("tree completion needs restart_every > 0 "
                             "(it corrects the noise at epoch boundaries)")
        if self.restart_every > 0 and next_pow2(self.restart_every) >= (1 << depth):
            raise ValueError(
                f"depth {depth} cannot cover the per-epoch horizon "
                f"{next_pow2(self.restart_every)} (restart_every="
                f"{self.restart_every})")

    def state_dict(self) -> dict:
        """The tree's node noise is a pure function of (seed, path, epoch,
        level, index), so the restorable state is the configuration that
        keys it. Depth is deliberately EXCLUDED: node draws are
        depth-invariant (levels above the prefix contribute i&1 == 0), so
        depth is a draw-cost knob, not part of the noise path."""
        return {"name": self.name, "seed": self.seed,
                "restart_every": self.restart_every,
                "completion": self.completion}

    def load_state(self, state: dict) -> None:
        """Validate that this mechanism continues the checkpointed release.
        A mismatched seed re-draws every released node; a mismatched
        restart period or completion flag shifts every epoch boundary —
        either silently voids the restart-exactness guarantee, so both
        raise."""
        mine = self.state_dict()
        drift = {k: (state.get(k), mine[k]) for k in mine
                 if state.get(k) != mine[k]}
        if drift:
            raise ValueError(
                "tree-noise state drift between checkpoint and resumed run "
                "(checkpointed != configured): "
                + ", ".join(f"{k}: {a!r} != {b!r}"
                            for k, (a, b) in sorted(drift.items())))

    def _node(self, path: str, level: int, idx, epoch=0):
        k = _path_rng(jax.random.PRNGKey(self.seed), path)
        k = jax.random.fold_in(k, epoch)
        return jax.random.fold_in(jax.random.fold_in(k, level), idx)

    def prefix_noise(self, path: str, shape, t, dtype=jnp.float32, epoch=0,
                     mesh=None, spec=None):
        """N_e(t): unit-variance-per-node cumulative noise for the epoch's
        steps [1..t]. With ``mesh``/``spec`` every node draw is shard-local
        (each device holds slice-sized node noise only)."""
        out = jnp.zeros(shape, dtype)
        for b in range(self.depth):
            i = t >> b
            z = sharded_normal(self._node(path, b, i, epoch), shape, dtype,
                               mesh=mesh, spec=spec)
            out = out + jnp.asarray(i & 1, dtype) * z
        return out

    def _epoch_local(self, step):
        """Global 0-indexed step -> (epoch, local 1-indexed prefix t)."""
        if self.restart_every <= 0:
            return 0, step + 1
        return step // self.restart_every, (step % self.restart_every) + 1

    def _local_prefix(self, sigma: float, step):
        """Validated (epoch, t, t_hi) for one call (shared by every leaf)."""
        if sigma > 0.0 and step is None:
            # a forgotten step would re-add the IDENTICAL N(1)-N(0) draw
            # every call — differences of released grads become noise-free.
            # Fail loudly instead of silently voiding the guarantee.
            raise ValueError(
                "tree aggregation is stateful: pass the step index — "
                "grad_fn(params, batch, rng, step) / engine.grad(..., step)")
        epoch, t = self._epoch_local(step if step is not None else 0)
        if isinstance(t, (int, np.integer)) and t >= (1 << self.depth):
            # past the horizon every level index t>>b goes even and N(t)
            # collapses toward zero — increments would SUBTRACT released
            # noise, silently voiding the guarantee. (Traced steps can't be
            # checked here; size depth from the run length as the train
            # driver does.)
            raise ValueError(
                f"step {t - 1} exceeds the tree horizon 2^depth-1 = "
                f"{(1 << self.depth) - 1}; raise depth (or set "
                "restart_every) to cover the run")
        t_hi = t
        if self.completion:
            # last step of the epoch: advance the prefix to the completed
            # tree so the FTRL restart rebases on single-root-node noise
            t_hi = jnp.where(t == self.restart_every,
                             next_pow2(self.restart_every), t)
        return epoch, t, t_hi

    def add_leaf(self, path: str, g, rng, sigma: float, scale,
                 denom: float, step=None, mesh=None, spec=None):
        del rng  # node noise keys off the fixed seed only
        epoch, t, t_hi = self._local_prefix(sigma, step)
        if sigma > 0.0:
            delta = (self.prefix_noise(path, g.shape, t_hi, epoch=epoch,
                                       mesh=mesh, spec=spec)
                     - self.prefix_noise(path, g.shape, t - 1, epoch=epoch,
                                         mesh=mesh, spec=spec))
            g = g + (sigma * scale) * delta.astype(g.dtype)
        return g / denom

    def add(self, flat_grads: dict, rng, sigma: float, sensitivity,
            denom: float, step=None, mesh=None, pspecs=None) -> dict:
        return {path: self.add_leaf(path, g, rng, sigma,
                                    _scale_for(sensitivity, path), denom,
                                    step=step, mesh=mesh,
                                    spec=_spec_of(pspecs, path))
                for path, g in flat_grads.items()}


NOISE_MECHANISMS = {
    "gaussian": GaussianMechanism,
    "tree": TreeAggregationMechanism,
}


def get_mechanism(name: str, seed: int = 0, depth: int | None = None,
                  restart_every: int = 0, completion: bool = False):
    """Build a registered mechanism. ``depth`` None/0 means "the mechanism's
    own default" (TreeAggregationMechanism keeps its 30) — the argument is a
    pass-through, never a clobber."""
    try:
        cls = NOISE_MECHANISMS[name]
    except KeyError:
        raise ValueError(f"unknown noise mechanism {name!r}; options: "
                         f"{sorted(NOISE_MECHANISMS)}")
    kw = {"seed": seed, "restart_every": restart_every,
          "completion": completion}
    if depth:  # 0/None -> keep the class default (regression: a depth-0 tree)
        kw["depth"] = depth
    return cls(**kw)
