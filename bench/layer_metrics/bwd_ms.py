"""bwd_ms (ms): device milliseconds per step in the backward half of the
step's forward and backward: paths with ``transpose(`` under ``bk_taps``
(BK's one transposed sweep for the tap cotangents) or ``grad`` (the plain
gradient's backward). Source: profiler trace, ``harness/phases.py``."""
from harness import phases


def read(ctx):
    return phases.ms_per_step(ctx, "bwd")
