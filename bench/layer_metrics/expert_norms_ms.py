"""expert_norms_ms (ms): device milliseconds per step of BK's per-sample
norms (``bk_norms``) of the expert taps (each in the scope of its ``moe``
tap key, ``core/bk.py`` ``tap_scope``): the grouped per-(expert, sample)
gradients and their squared norms. None where the step has no expert tap.
Source: profiler trace, ``harness/scopes.py``."""
from harness import scopes


def read(ctx):
    return scopes.ms_per_step(ctx, scopes.tap_work("bk_norms", "moe"))
