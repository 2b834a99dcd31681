"""Program tracing: named phases of the fabric step on the device, spans
around the host work of the user API, and a count of retraces per jitted
entry point.

Tracing is on exactly when a JAX profiler runs (``jax.profiler.trace``,
``start_trace`` or a capture through a profiler server); there is no
option. A host span is a ``jax.profiler.TraceAnnotation``: with no
profiler running it is a no-op, and with one the profiler keeps the event
and writes it out when the trace stops, on the clock it puts the device's
ops on. A device scope is a ``jax.named_scope``: it only names the HLO
instructions traced inside it (their ``op_name`` metadata), so the
compiled program is the same with and without it.

Device scopes (:data:`SCOPES`) follow the per-slice step of
:mod:`repro.core.fabric`; an op outside every phase of the step carries
the bare ``fabric`` scope. :data:`EXCHANGE` wraps the collectives of the
sharded step wherever they are called, so it nests under whichever phase
called it (``fabric/hop/admit/exchange``). A phase with no work in a
program leaves no op behind: ``fabric/finish`` exists only with telemetry
on or sharded, and the compact views (``compact``, ``scatter_back``) only
in a single-device, unbatched program with more packets than the smallest
view (2,048).

:data:`BACKLOG_GATHER` is not a phase but a counter, nested in
``fabric/hop/backlog_filter``: the gather of each packet's backlog cut,
which runs inside a conditional branch, in the hops after the first where
some group holds a cut (so only with more than one hop per slice). Its
executions over slices x (hops - 1) are the share of hops the filter
engaged. A reducer that knows only the names of :data:`SCOPES` counts its
time in ``fabric/hop/backlog_filter``.

Host spans (names in ``docs/api/core.tracing.md``): ``OpenOpticsNet.run``
around the whole call, with ``run.tables``, ``run.masks``,
``run.to_device``, ``run.dispatch``, ``run.device_wait``,
``run.result_copy`` and ``run.traffic_matrix`` inside;
``OpenOpticsNet.ingest`` (``ingest.concat``), ``OpenOpticsNet.advance``
(``advance.dispatch``, ``advance.device_wait``, ``advance.stats_copy``)
and ``OpenOpticsNet.snapshot``. The ``run.*`` spans from ``run.to_device``
on are opened by :func:`repro.core.fabric.simulate` and the ``advance.*``
ones by :func:`repro.core.fabric.step_slices`, so direct callers of those
get them too.
"""
from __future__ import annotations

import collections

import jax

__all__ = ["SCOPES", "BACKLOG_GATHER", "EXCHANGE", "retraces", "span",
           "retrace"]

SCOPES = (
    "fabric/activate",                 # phase 0: activating queues leave occ
    "fabric/inject",                   # phases 1+2: injection and re-lookup
    "fabric/inject/lookup",
    "fabric/inject/compact",
    "fabric/inject/scatter_back",
    "fabric/inject/enqueue",
    "fabric/hop",                      # phase 3, each unrolled hop
    "fabric/hop/backlog_filter",
    "fabric/hop/compact",
    "fabric/hop/scatter_back",
    "fabric/hop/admit",
    "fabric/hop/lookup",
    "fabric/hop/reorder",
    "fabric/hop/enqueue",
    "fabric/missed",                   # phase 4
    "fabric/stats",                    # phase 5 and the telemetry rows
    "fabric/finish",                   # the result after the scan
)
BACKLOG_GATHER = "fabric/hop/backlog_filter/gather"
EXCHANGE = "exchange"

# traces of each jitted entry point's Python body, which runs only when JAX
# traces it (a new shape or static argument), so counting costs no call
retraces: collections.Counter = collections.Counter()


def span(name: str) -> jax.profiler.TraceAnnotation:
    """A host span named ``name`` (a context manager)."""
    return jax.profiler.TraceAnnotation(name)


def retrace(fn: str) -> jax.profiler.TraceAnnotation:
    """Count one trace of the jitted entry point ``fn``; the span
    ``retrace/<fn>`` to open around its body."""
    retraces[fn] += 1
    return span(f"retrace/{fn}")
