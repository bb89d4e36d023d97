"""update_ms (ms): device milliseconds per step in ``update``: the noise
and the optimizer's pass over the leaves (AdamW). Source: profiler trace,
``harness/phases.py``."""
from harness import phases


def read(ctx):
    return phases.ms_per_step(ctx, "update")
