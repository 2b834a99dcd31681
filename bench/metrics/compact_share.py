"""Share of device busy time in phase compaction and scatter-back, in %:
the ops under every ``.../compact`` (gathering a phase's packets into a
compact view) and ``.../scatter_back`` (writing the view back), read by
``bench/program_trace.py``. Nothing to read in a program without
scopes."""
from bench.program_trace import scope_share


def read(ctx):
    if ctx.trace is None:
        return None
    return scope_share(ctx.trace, "fabric/inject/compact",
                       "fabric/inject/scatter_back", "fabric/hop/compact",
                       "fabric/hop/scatter_back")
