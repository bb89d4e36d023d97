"""fwd_ms (ms): device milliseconds per step in the forward half of the
step's forward and backward: ``bk_taps`` (BK's tapped forward, under
``jax.linearize``) or ``grad`` (the plain ``value_and_grad``), paths
without ``transpose(``. Source: profiler trace, ``harness/phases.py``."""
from harness import phases


def read(ctx):
    return phases.ms_per_step(ctx, "fwd")
