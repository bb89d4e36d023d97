"""expert_matmul_ms (ms): device milliseconds per step of the expert
layer's grouped matmuls, in the forward, the backward and the
recomputation: the ops under its ``moe_experts`` scope (``models/moe.py``:
the matmuls and the gate between them) and the TPU's ragged-dot kernels,
which XLA makes under names of its own, but for those of BK's expert norms
and clipped sums (``expert_norms_ms``, ``expert_clipped_sum_ms``). None
where the step has no expert layer. Source: profiler trace,
``harness/scopes.py``."""
from harness import scopes


def read(ctx):
    return scopes.ms_per_step(ctx, scopes.but(
        scopes.either(scopes.under("moe_experts"),
                      scopes.xla_op("ragged-dot")),
        scopes.tap_work("bk_norms", "moe"),
        scopes.tap_work("bk_clipped_sum", "moe")))
