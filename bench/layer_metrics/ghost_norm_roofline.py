"""ghost_norm_roofline (%): the least time of the ghost-norm operations the
trace ran (bench/kernels/ghost_norm.py, peaks by device kind) over the
summed device time of the kernel's events. None when the step runs none."""


def read(ctx):
    return ctx.trace["roofline"].get("ghost_norm")
