"""expert_clipped_sum_ms (ms): device milliseconds per step of BK's
weighted gradients (``bk_clipped_sum``) of the expert taps (each in the
scope of its ``moe`` tap key): per held expert the clip-weighted sum over
its rows. None where the step has no expert tap. Source: profiler trace,
``harness/scopes.py``."""
from harness import scopes


def read(ctx):
    return scopes.ms_per_step(ctx, scopes.tap_work("bk_clipped_sum", "moe"))
