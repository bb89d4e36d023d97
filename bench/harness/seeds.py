"""Keys and weights made from ``--seed``, shared by the harness and the
plain reference, so both start from the same numbers without either taking
them from the program.

A seed may exceed 32 bits; ``jax.random.PRNGKey`` keeps only the low word
of such a seed, so the raw threefry key is built here from both words.
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp
import numpy as np

SALTS = {"weights": 0x5EED0001, "data": 0x5EED0002, "step": 0x5EED0003}
INIT_STD = 0.02       # the published initializer_range of both Qwen configs


def raw_key(seed: int, purpose: str) -> jax.Array:
    """-> uint32[2] threefry key for (seed, purpose); any seed < 2**64."""
    if not 0 <= seed < 1 << 64:
        raise ValueError(f"seed must lie in [0, 2**64), got {seed}")
    hi, lo = (seed >> 32) & 0xFFFFFFFF, seed & 0xFFFFFFFF
    key = np.array([hi, lo], np.uint32)
    return jax.random.fold_in(jnp.asarray(key), SALTS[purpose])


def path_key(key, path: str):
    return jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)


def rounded(x, dtype):
    """f32 ``x`` rounded to the precision of ``dtype``, as that dtype.

    The rounding is an explicit ``reduce_precision``: inside a jitted
    function XLA may drop a plain f32 -> bf16 -> f32 round trip (excess
    precision is allowed by default), which would leave values a bf16
    store never holds."""
    if jnp.dtype(dtype) == jnp.bfloat16:
        x = jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
    return x.astype(dtype)


def init_leaf(key, path: str, shape, dtype):
    """One leaf of the seeded init: norm gains are 1, every other leaf
    N(0, INIT_STD^2), drawn in f32 and rounded to the stored dtype."""
    if path.endswith("/g"):
        return jnp.ones(shape, dtype)
    x = jax.random.normal(path_key(key, path), shape, jnp.float32)
    return rounded(x * INIT_STD, dtype)


def init_flat(seed: int, shapes: dict, dtype, out_shardings=None) -> dict:
    """{path: shape} -> {path: array}, all leaves in one jitted call, made
    on the device (placed by ``out_shardings`` when given)."""
    key = raw_key(seed, "weights")

    def make(k):
        return {p: init_leaf(k, p, s, dtype) for p, s in shapes.items()}

    return jax.jit(make, out_shardings=out_shardings)(key)
