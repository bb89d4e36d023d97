"""expert_dispatch_ms (ms): device milliseconds per step under the expert
layer's ``moe_dispatch`` scope (``models/moe.py``: router, top-k, the
layout of rows by expert and sample, the gather into rows and the weighted
combine), in the forward, the backward and the recomputation. None where
the step has no expert layer. Source: profiler trace,
``harness/scopes.py``."""
from harness import scopes


def read(ctx):
    return scopes.ms_per_step(ctx, scopes.under("moe_dispatch"))
