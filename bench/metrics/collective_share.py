"""Share of device busy time in collective ops (all-reduce, all-gather
and the like, including their async start and done halves), in %: the
shard exchange of ``simulate_sharded``. Nothing to read on one chip."""

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")


def read(ctx):
    if ctx.trace is None or ctx.trace["devices"] < 2:
        return None
    coll = sum(s for code, s in ctx.trace["opcode_s"].items()
               if code.startswith(COLLECTIVES))
    if coll <= 0:
        return None
    return 100.0 * coll / ctx.trace["busy_s"]
