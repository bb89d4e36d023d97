"""Production mesh construction.

Single pod: (data=16, model=16) — 256 chips of TPU v5e.
Multi-pod:  (pod=2, data=16, model=16) — 512 chips; the pod axis is pure
data parallelism over DCN (gradient all-reduce optionally 8-bit compressed,
see repro.runtime.compression), FSDP+TP live on the ICI axes.

Defined as functions (never module-level) so importing this module does not
touch jax device state — the dry-run sets XLA_FLAGS before first jax use.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with every axis ``Auto``: the engine's ``shard_map``
    calls and ``in/out_shardings`` leave propagation to GSPMD. (Since JAX
    0.9 the default is ``Explicit``, under which the embedding gather of a
    batch-sharded id array raises ``ShardingTypeError``.)"""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_train_mesh(data: int = 0, model: int = 1):
    """Mesh for the real train driver, sized to whatever devices exist:
    (data=N/model, model) — one CPU gives the degenerate (1, 1) mesh, so
    every train() call runs the same mesh-lowered jit path regardless of
    topology. ``data=0`` means "all remaining devices"."""
    n = len(jax.devices())
    if model <= 0:
        model = 1
    if data <= 0:
        if n % model:
            raise ValueError(
                f"model axis {model} does not divide {n} devices "
                "(pass an explicit data size to use a subset)")
        data = n // model
    if data * model > n:
        raise ValueError(f"mesh ({data}, {model}) needs {data * model} "
                         f"devices, have {n}")
    return make_mesh((data, model), ("data", "model"),
                     devices=jax.devices()[:data * model])


def batch_axes(mesh) -> tuple:
    """Axes the batch dim shards over (pod included when present)."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))
