"""bk_norms_ms (ms): device milliseconds per step in BK's per-sample norms
(``bk_norms``: each tap's squared norms, the vector parameters' norms, the
clip factors). None where the step runs no BK. Source: profiler trace,
``harness/phases.py``."""
from harness import phases


def read(ctx):
    return phases.ms_per_step(ctx, "bk_norms")
