"""Plain float32 reference of the first training steps of a cell, on the
model of the reference module that the configuration names.

Per step, one row at a time: the row's own gradient (``jax.grad`` of its
loss) -> per clipping unit, that row's norm and automatic clip factor
R / (norm + gamma) -> the clipped gradient, added to the clipped sum in
the same pass. Then Gaussian noise of std sigma * sqrt(sum of R^2) -> divided
by the batch -> AdamW. A non-private cell takes the plain mean gradient
instead. Parameters are stored in the configuration's bf16 between steps,
as bf16 arrays, so that no compiler keeps them wider; all arithmetic is
f32. AdamW's moments wait on the host between steps, so that the reference
fits one chip beside its own per-row gradients.

``precision="float8"`` is the control: the same code with every matrix
operand and each step's gradient rounded through float8_e4m3fn, the step
below the bf16 the configurations state. The round trip sits behind an
optimization barrier: inside a jitted function XLA may otherwise drop it.

Faults for calibrating the comparison, each applied as the program would
suffer it: ``half`` (the second half of every batch replaced by the first,
so the mean is over half the rows), ``exchange`` (the clipped sum holds only
the rows of the first of ``data_chips`` chips), ``token`` (one token of row
0 altered where it is produced).
"""
from __future__ import annotations

import functools
import math
import re

import jax
import jax.numpy as jnp
import numpy as np

from harness import seeds
from reference import noise, reference_for

F32 = jnp.float32


def _f8(x):
    """x rounded through float8_e4m3fn. The barrier keeps XLA from dropping
    the round trip, as it may inside a jitted function."""
    return jax.lax.optimization_barrier(
        x.astype(jnp.float8_e4m3fn)).astype(F32)


LOWER = {"float32": lambda x: x, "float8": _f8}


def fault_tokens(tokens, fault: str, vocab: int):
    """The batch a faulty program would see (host or device array)."""
    tokens = jnp.asarray(tokens)
    if fault == "half":
        h = tokens.shape[0] // 2
        return jnp.concatenate([tokens[:h], tokens[:h]], axis=0)
    if fault == "token":
        T = tokens.shape[1]
        return tokens.at[0, T // 2].set((tokens[0, T // 2] + 1)
                                        % vocab)
    return tokens


def clip_units(groups: list, paths) -> tuple:
    """First-matching group per leaf -> (unit index per path, units). Flat
    groups share one unit; each 'group'-scope group is its own."""
    units, unit_of = [], {}
    flat_unit = None
    for path in paths:
        g = next(g for g in groups if re.fullmatch(g["match"], path))
        if g["scope"] == "flat":
            if flat_unit is None:
                flat_unit = len(units)
                units.append(g)
            unit_of[path] = flat_unit
        elif g["scope"] == "group":
            name = g["name"]
            idx = next((i for i, u in enumerate(units)
                        if u["name"] == name and u["scope"] == "group"), None)
            if idx is None:
                idx = len(units)
                units.append(g)
            unit_of[path] = idx
        else:
            raise ValueError(f"scope {g['scope']!r} has no reference")
    return unit_of, units


class DPReference:
    def __init__(self, cfg: dict, traffic: dict, precision: str = "float32",
                 device=None):
        self.cfg, self.tr = cfg, traffic
        model = reference_for(cfg)
        self.shapes = model.param_shapes(cfg)
        self.paths = sorted(self.shapes)
        self.private = traffic["mode"] != "nonprivate"
        self.unit_of, self.units = clip_units(cfg["dp_groups"], self.paths)
        self.sens = math.sqrt(sum(u["R"] ** 2 for u in self.units))
        self.lo = LOWER[precision]
        self.device = device or jax.devices()[0]
        loss = functools.partial(model.row_loss, cfg=cfg, lo=self.lo)
        uidx = {p: self.unit_of[p] for p in self.paths}
        nu = len(self.units)

        def f32(params):
            return {p: x.astype(F32) for p, x in params.items()}

        R = jnp.asarray([u["R"] for u in self.units], F32)
        gam = jnp.asarray([u["gamma"] for u in self.units], F32)
        private = self.private

        def row_step(S, params, row, w):
            """One row: its loss and gradient, its clip factor per unit
            from its own norms (automatic clipping needs no other row),
            and the clipped gradient, weighted by ``w``, added to S."""
            l, g = jax.value_and_grad(loss)(f32(params), row)
            if private:
                sq = [jnp.zeros((), F32) for _ in range(nu)]
                for path in self.paths:
                    sq[uidx[path]] = sq[uidx[path]] + jnp.sum(
                        jnp.square(g[path]))
                c = w * R / (jnp.sqrt(jnp.stack(sq)) + gam)
            else:
                c = w * jnp.ones((nu,), F32)
            return l, {p: S[p] + c[uidx[p]] * g[p] for p in self.paths}

        self._row_step = jax.jit(row_step, donate_argnums=(0,))
        tr = traffic
        b1, b2, eps, lr = tr["b1"], tr["b2"], tr["eps"], tr["lr"]
        lo = self.lo

        def leaf_update(p, S, m, v, t, key, B, scale):
            n = (scale * noise.standard_normal(key, p.shape)
                 if self.private else jnp.zeros(p.shape, F32))
            g = lo((S + n) / B)
            m = b1 * m + (1 - b1) * g
            v = b2 * v + (1 - b2) * jnp.square(g)
            tf = t.astype(F32) + 1.0
            upd = (m / (1 - b1 ** tf)) / (jnp.sqrt(v / (1 - b2 ** tf)) + eps)
            p_new = seeds.rounded(p.astype(F32) - lr * upd, jnp.bfloat16)
            return p_new, m, v, jnp.sqrt(jnp.sum(g * g)), \
                jnp.sqrt(jnp.sum(S * S)), n, g

        def proj(g0p, S, n, B, s_norm):
            """<B g0p - n, S> / |S|: the run's first gradient, noise taken
            out, along this reference's clipped sum."""
            return jnp.sum((B * g0p.astype(F32) - n) * S) / jnp.maximum(
                s_norm, 1e-30)

        self._proj = jax.jit(proj)
        self._leaf_update = jax.jit(leaf_update, donate_argnums=(0, 2, 3))

    # ------------------------------------------------------------------
    def run(self, seed: int, batch_for, steps: int, fault: str = "",
            prog_g0: dict | None = None, keep_g0: bool = False,
            data_chips: int = 1) -> dict:
        """-> readings of the first ``steps`` steps from ``seed``.

        ``batch_for(step)`` gives that step's (B, T) tokens. ``prog_g0``
        ({run name: {path: host array}}) holds the first averaged gradient
        of each run under test: reading ``proj[name]`` is its projection,
        noise removed, on this reference's clipped sum."""
        dev = self.device
        put = lambda x: jax.device_put(x, dev)
        # stored in bf16, as the configuration states; used in f32
        params = jax.device_put(
            seeds.init_flat(seed, self.shapes, jnp.bfloat16), dev)
        base = put(seeds.raw_key(seed, "step"))
        m_host, v_host = None, None
        prog_g0 = prog_g0 or {}
        out = {"losses": [], "g0_norm": {}, "s_norm": {},
               "proj": {name: {} for name in prog_g0}, "g0": {}}
        for t in range(steps):
            tokens = put(fault_tokens(batch_for(t), fault,
                                      self.cfg["vocab_size"]))
            B = tokens.shape[0]
            losses = []
            S = {p: jnp.zeros(self.shapes[p], F32) for p in self.paths}
            rows = B // data_chips if fault == "exchange" else B
            for i in range(B):
                l, S = self._row_step(S, params, tokens[i],
                                      F32(1.0 if i < rows else 0.0))
                losses.append(l)
            out["losses"].append(float(jnp.mean(jnp.stack(losses))))
            new_m, new_v = {}, {}
            for p in self.paths:
                shape = self.shapes[p]
                m = (put(m_host[p]) if m_host is not None
                     else jnp.zeros(shape, F32))
                v = (put(v_host[p]) if v_host is not None
                     else jnp.zeros(shape, F32))
                key = noise.leaf_key(base, t, p)
                s_leaf = S.pop(p)
                pn, m, v, gn, sn, n, g = self._leaf_update(
                    params[p], s_leaf, m, v, jnp.asarray(t, jnp.int32), key,
                    F32(B), F32(self.tr["sigma"] * self.sens))
                params[p] = pn
                if t == 0:
                    out["g0_norm"][p] = float(gn)
                    out["s_norm"][p] = float(sn)
                    for name, g0 in prog_g0.items():
                        out["proj"][name][p] = float(self._proj(
                            put(g0[p]), s_leaf, n, F32(B), sn))
                    if keep_g0:
                        out["g0"][p] = np.asarray(g.astype(jnp.bfloat16))
                del s_leaf, n, g
                if t < steps - 1:
                    new_m[p], new_v[p] = np.asarray(m), np.asarray(v)
                del m, v
            m_host, v_host = new_m, new_v
        p0 = jax.device_put(seeds.init_flat(seed, self.shapes, jnp.bfloat16),
                            dev)
        out["upd_norm"] = {
            p: float(jnp.sqrt(jnp.sum(jnp.square(
                params[p].astype(F32) - p0[p].astype(F32)))))
            for p in self.paths}
        return out
