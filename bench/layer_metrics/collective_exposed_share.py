"""collective_exposed_share (%): the share of the traced window in which a
collective runs on a chip and no other operation does, averaged over the
chips. None on one chip, where no collective runs."""


def read(ctx):
    red = ctx.trace
    if ctx.chips < 2 or red["window_s"] <= 0:
        return None
    return 100.0 * red["collective_exposed_s"] / red["window_s"]
