"""bk_clipped_sum_ms (ms): device milliseconds per step in BK's weighted
gradients (``bk_clipped_sum``: each tap's a^T diag(C) ds, the vector
parameters' clipped sums). None where the step runs no BK. Source:
profiler trace, ``harness/phases.py``."""
from harness import phases


def read(ctx):
    return phases.ms_per_step(ctx, "bk_clipped_sum")
