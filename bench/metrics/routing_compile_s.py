"""Host seconds of the routing scheme's table compile in set-up (the
control plane: ``vlb(...)`` / ``ucmp(...)`` in ``repro.core.routing``)."""


def read(ctx):
    return ctx.setup.get("routing_compile_s")
