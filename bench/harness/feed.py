"""The training feed: one general generator driven by a traffic file.

Every row is a run of consecutive token ids (mod vocab) from a random start,
with a share ``outlier_frac`` of positions replaced by uniform ids: text the
model can learn from, all rows different. Step ``t``'s batch is a pure
function of (seed, t), made on the device in one jitted call.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from harness import seeds


def make_tokens(key, step, B: int, T: int, vocab: int, outlier_frac: float):
    k = jax.random.fold_in(key, step)
    k_start, k_keep, k_rare = jax.random.split(k, 3)
    runs = (jnp.arange(T)[None, :]
            + jax.random.randint(k_start, (B, 1), 0, vocab)) % vocab
    rare = jax.random.randint(k_rare, (B, T), 0, vocab)
    keep = jax.random.uniform(k_keep, (B, T)) >= outlier_frac
    return jnp.where(keep, runs, rare).astype(jnp.int32)


class Feed:
    """feed(step) -> {"tokens": (B, T) int32}, placed by ``sharding``."""

    def __init__(self, seed: int, B: int, T: int, vocab: int,
                 outlier_frac: float, sharding=None):
        self.key = seeds.raw_key(seed, "data")
        self._gen = jax.jit(
            lambda key, step: make_tokens(key, step, B, T, vocab,
                                          outlier_frac),
            out_shardings=sharding)

    def tokens(self, step: int):
        return self._gen(self.key, jnp.asarray(step, jnp.int32))

    def __call__(self, step: int) -> dict:
        return {"tokens": self.tokens(step)}
