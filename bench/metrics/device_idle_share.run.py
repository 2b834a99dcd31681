"""Share of the traced window in which the device ran nothing, in %
(1 - busy / window, busy averaged over the chips used)."""


def read(ctx):
    if ctx.trace is None:
        return None
    return 100.0 * (1.0 - ctx.trace["busy_s"] / ctx.trace["window_s"])
