"""The Gaussian noise of one DP step, written from its definition.

The program draws the noise of parameter leaf ``path`` at step ``t`` from the
key ``fold_in(fold_in(base, t), crc32(path) & 0x7FFFFFFF)``: element ``i`` (in
row-major order) is the inverse normal CDF of the top 24 bits of the
threefry-2x32 block of ``(key, counter = (i, 0))``, shifted to the centre of
their cell, with the top cell held below 1. The reference draws the same
numbers here, so that the noise cancels out of the comparison and the
clipped sum underneath can be checked.
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp
from jax.extend.random import threefry2x32_p
from jax.scipy.special import ndtri


def leaf_key(base, step, path: str):
    k = jax.random.fold_in(base, step)
    return jax.random.fold_in(k, zlib.crc32(path.encode()) & 0x7FFFFFFF)


def standard_normal(key, shape):
    """-> f32 N(0, 1) of ``shape`` (fewer than 2**32 elements)."""
    n = 1
    for s in shape:
        n *= int(s)
    if n >= 1 << 32:
        raise ValueError(f"leaf of {n} elements: the counter needs both words")
    lo = jax.lax.iota(jnp.uint32, n)
    hi = jnp.zeros_like(lo)
    bits, _ = threefry2x32_p.bind(jnp.broadcast_to(key[0], lo.shape),
                                  jnp.broadcast_to(key[1], lo.shape), lo, hi)
    u = (bits >> jnp.uint32(8)).astype(jnp.float32) * jnp.float32(2 ** -24) \
        + jnp.float32(2 ** -25)
    u = jnp.minimum(u, jnp.float32(1 - 2 ** -24))
    return ndtri(u).reshape(shape)
