"""Block layouts Mosaic accepts for the kernels' per-sample scalars.

The TPU lowering refuses a rank-1 ``(1,)`` block of a ``(B,)`` array (a
rank-1 block must be the whole array or a multiple of 128 lanes), which is
the natural block of a per-sample clip factor or squared norm. Inputs
therefore sit whole in SMEM and are read as ``ref[b]``; outputs are written
lane-dense, one ``(1, 1, 128)`` row per sample, and sliced back to ``(B,)``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128


def scalar_in_spec() -> pl.BlockSpec:
    """Spec of a (B,) per-sample scalar input: the whole vector in SMEM."""
    return pl.BlockSpec(memory_space=pltpu.SMEM)


def scalar_out(B: int, index_map):
    """(spec, shape) of a (B,) f32 per-sample output: ``index_map`` returns
    the sample index; the kernel broadcasts its scalar over the block's
    lanes and :func:`scalar_rows` takes it back."""
    spec = pl.BlockSpec((1, 1, LANES),
                        lambda *g: (index_map(*g), 0, 0))
    return spec, jax.ShapeDtypeStruct((B, 1, LANES), jnp.float32)


def scalar_rows(out):
    """(B, 1, 128) lane-dense rows -> (B,)."""
    return out[:, 0, 0]
