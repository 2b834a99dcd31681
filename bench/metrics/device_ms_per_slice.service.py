"""Device busy milliseconds per simulated slice in the traced window: the
union of the device's op intervals (averaged over the chips used) over
the slices that the window's calls simulated."""


def read(ctx):
    if ctx.trace is None or not ctx.slices:
        return None
    return 1e3 * ctx.trace["busy_s"] / ctx.slices
