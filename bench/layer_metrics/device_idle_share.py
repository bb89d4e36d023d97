"""device_idle_share (%): 1 - (union of the device's op intervals / the
traced window), averaged over the cell's chips. Source: profiler trace."""


def read(ctx):
    red = ctx.trace
    if red["window_s"] <= 0 or red["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])
