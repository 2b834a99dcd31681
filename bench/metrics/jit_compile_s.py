"""Seconds JAX spent in set-up tracing, lowering and compiling the
program or loading it from the persistent cache (JAX's own
``/jax/core/compile/*_duration`` events; a cache load is timed inside the
backend-compile event). On a run whose programs are all cached this is
the cache's cost, not zero."""


def read(ctx):
    return ctx.setup.get("jit_compile_s")
