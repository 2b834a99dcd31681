"""Share of device busy time in the hop's backlog filter, in %: the ops
under the scope ``fabric/hop/backlog_filter`` (``want0`` and the gather of
each packet's group cut, ``backlog_min[key_all]``, at full width every
hop), read by ``bench/program_trace.py``. Nothing to read in a program
without scopes."""
from bench.program_trace import scope_share


def read(ctx):
    if ctx.trace is None:
        return None
    return scope_share(ctx.trace, "fabric/hop/backlog_filter")
