"""Share of device busy time in time-flow table lookups, in %: the ops
under ``fabric/inject/lookup`` (the fused injection and re-lookup gather)
and ``fabric/hop/lookup`` (the transit lookup), read by
``bench/program_trace.py``. Nothing to read in a program without
scopes."""
from bench.program_trace import scope_share


def read(ctx):
    if ctx.trace is None:
        return None
    return scope_share(ctx.trace, "fabric/inject/lookup", "fabric/hop/lookup")
