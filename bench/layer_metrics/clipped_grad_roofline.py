"""clipped_grad_roofline (%): as ghost_norm_roofline, for the clipped
weighted-gradient kernel (bench/kernels/clipped_grad.py)."""


def read(ctx):
    return ctx.trace["roofline"].get("clipped_grad")
