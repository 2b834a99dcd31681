"""Share of device busy time in queue admission, in %: the ops under
``fabric/hop/admit``, every ``_admit`` call of the hop (the whole
admission, its sort and prefix sums included, and the rejection minima it
leaves for the next hop's filter), read by ``bench/program_trace.py``.
Nothing to read in a program without scopes."""
from bench.program_trace import scope_share


def read(ctx):
    if ctx.trace is None:
        return None
    return scope_share(ctx.trace, "fabric/hop/admit")
