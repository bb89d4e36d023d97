"""The system under test, as the benchmark drives it: the donated train
step that ``repro.launch.train.train`` runs, built through
``launch.steps.make_train_step`` on the ``launch.mesh.make_train_mesh``
mesh. This is the only module of the benchmark that imports the program.
"""
from __future__ import annotations

import os
import sys

import jax
import jax.numpy as jnp

from harness import seeds
from reference import reference_for


class ProgramMissing(RuntimeError):
    """The checkout holds no importable ``repro`` package."""


def import_program(root: str):
    src = os.path.join(root, "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    try:
        import repro.launch.steps  # noqa: F401
    except ImportError as e:
        raise ProgramMissing(f"no program beside the benchmark ({e})")


def flatten(tree, prefix="") -> dict:
    out = {}
    for k, v in tree.items():
        path = f"{prefix}/{k}" if prefix else k
        if isinstance(v, dict):
            out.update(flatten(v, path))
        else:
            out[path] = v
    return out


def unflatten(flat: dict) -> dict:
    out = {}
    for path, v in flat.items():
        node = out
        *head, last = path.split("/")
        for k in head:
            node = node.setdefault(k, {})
        node[last] = v
    return out


def model_config(cfg: dict):
    """The program's ModelConfig for a benchmark configuration file: the
    arch's registered config at the sizes the file states, checked against
    what else the file states; the file's reference maps the one onto the
    other."""
    from repro.configs.registry import get_config
    sizes, stated = reference_for(cfg).program_config(cfg)
    mc = get_config(cfg["arch"]).with_(**sizes)
    wrong = {k: (getattr(mc, k), v) for k, v in stated.items()
             if getattr(mc, k) != v}
    if wrong:
        raise ValueError(f"{cfg['arch']}: the program's config differs from "
                         f"the benchmark's (program, stated): {wrong}")
    return mc


def dp_policy(cfg: dict, traffic: dict):
    """The program's DP config for the cell: the arch's registered preset,
    which must clip as ``dp_groups`` says, or one flat group."""
    from repro.configs.registry import get_policy, has_policy
    from repro.core.bk import DPConfig
    groups = cfg["dp_groups"]
    mode, sigma = traffic["mode"], traffic["sigma"]
    if has_policy(cfg["arch"]):
        pol = get_policy(cfg["arch"], mode=mode, sigma=sigma)
        got = [dict(name=g.name, match=g.match, clipping=g.clipping, R=g.R,
                    scope=g.scope, gamma=g.gamma) for g in pol.groups]
        bad = [g for g in pol.groups
               if not g.trainable or g.sigma_scale != 1.0 or g.method]
        if got != groups or bad or pol.noise != "gaussian":
            raise ValueError(f"{cfg['arch']}: registered policy {got} is not "
                             f"the benchmark's {groups}")
        return pol
    if len(groups) != 1 or groups[0]["scope"] != "flat":
        raise ValueError(f"{cfg['arch']} has no registered policy; only one "
                         "flat group can be stated")
    g = groups[0]
    return DPConfig(mode=mode, clipping=g["clipping"], R=g["R"],
                    gamma=g["gamma"], sigma=sigma)


class TrainStep:
    """One compiled donated step with its state, made from the seed."""

    def __init__(self, cfg: dict, traffic: dict, seed: int):
        from repro.configs.registry import build
        from repro.launch.mesh import make_train_mesh
        from repro.launch.steps import TrainState, make_train_step
        from repro.optim.optimizers import make_optimizer

        mc = model_config(cfg)
        self.model = build(mc)
        chips = traffic["data_chips"]
        self.B = traffic["batch_per_chip"] * chips
        self.T = traffic["seq"]
        self.mesh = make_train_mesh(chips, 1)
        lr = traffic["lr"]
        if (traffic["b1"], traffic["b2"], traffic["eps"]) != (0.9, 0.999,
                                                              1e-8):
            raise ValueError("the program's AdamW is fixed at b1 0.9, "
                             "b2 0.999, eps 1e-8")
        self.opt = make_optimizer(traffic["optimizer"],
                                  lambda s: jnp.asarray(lr, jnp.float32))
        p_struct = jax.eval_shape(self.model.init,
                                  jax.ShapeDtypeStruct((2,), jnp.uint32))
        shapes = {p: tuple(s.shape) for p, s in flatten(p_struct).items()}
        if shapes != reference_for(cfg).param_shapes(cfg):
            raise ValueError("the program's parameter layout differs from "
                             "the reference's")
        b_struct = {"tokens": jax.ShapeDtypeStruct((self.B, self.T),
                                                   jnp.int32)}
        step_fn, self.state_sh, self.batch_sh = make_train_step(
            self.model.apply, p_struct, self.opt, traffic["optimizer"],
            dp_policy(cfg, traffic), traffic["microbatch"], self.mesh,
            b_struct)
        params = unflatten(seeds.init_flat(
            seed, shapes, jnp.dtype(cfg["torch_dtype"]),
            out_shardings=flatten(self.state_sh.params)))
        opt_state = jax.jit(self.opt.init,
                            out_shardings=self.state_sh.opt_state)(params)
        self.state = TrainState(
            params=params, opt_state=opt_state,
            step=jnp.asarray(0, jnp.int32),
            rng=jax.device_put(seeds.raw_key(seed, "step"),
                               self.state_sh.rng))
        jitted = jax.jit(step_fn, in_shardings=(self.state_sh, self.batch_sh),
                         out_shardings=(self.state_sh, None),
                         donate_argnums=(0,))
        with self.mesh:
            self.compiled = jitted.lower(self.state, b_struct).compile()

    def __call__(self, batch):
        with self.mesh:
            self.state, loss = self.compiled(self.state, batch)
        return loss

    def hlo_text(self) -> str:
        return self.compiled.as_text()

    def temp_bytes(self) -> int:
        return int(self.compiled.memory_analysis().temp_size_in_bytes)

    def free(self):
        """Drop the state and the executable before the reference runs."""
        jax.tree_util.tree_map(lambda x: x.delete(), self.state)
        self.state = None
        self.compiled = None
