"""Mean host milliseconds per ``OpenOpticsNet.snapshot()`` call in the
window, on the benchmark's own span around it."""


def read(ctx):
    t = ctx.spans.seconds("snapshot", since=ctx.window[0])
    return 1e3 * sum(t) / len(t) if t else None
