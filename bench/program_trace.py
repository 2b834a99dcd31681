"""Reduction of the program's own names in a profiler trace of the measured
window: device time by phase of the fabric step (the ``jax.named_scope``
tree of ``repro.core.tracing``) and the user API's host spans
(``jax.profiler.TraceAnnotation``). A program without them, such as one
from before they existed, reduces to empty maps, and every reader of
these keys then has nothing to read.

    python3 bench/program_trace.py --workload <cell> --seed <n> --seconds <s>

runs the cell once with the window traced (on a TPU, like ``run.py``),
keeps the trace until both reductions have read it, and prints one JSON
line: the run's result line, the readers of ``PROGRAM_METRICS`` on the
union of both reductions, the keys below, the traced window's
``pkt_slices_per_s`` and this reduction's wall time.

Where an op's scope comes from: the TPU trace gives each device op's
event the op's HLO text (``%fusion.12 = ...``), not its ``op_name``
(the v5e trace has no ``tf_op`` statistic). The ``/host:metadata`` plane
holds each program's optimised HLO (the ``Hlo Proto`` statistic of the
event metadata named after the program, ``jit__simulate_jit(<id>)``), and
each instruction there carries its ``metadata.op_name``, which JAX writes
from the name stack (``jit(_simulate_jit)/.../fabric/hop/.../admit/sort``).
An op event is matched to its program by the ``XLA Modules`` event around
it on the same device, and to its instruction by the name before `` = ``.
A fusion is one instruction, with the ``op_name`` of its root (XLA copies
the root's metadata onto the fusion it builds), so a fusion takes the
scope of its root. The HLO is read once per program, not once per event.

An op's scope path is the segments of its ``op_name`` from ``fabric`` on
that name a scope (``SCOPE_WORDS``). Two cases take the scope of the
innermost op event around them (a loop or conditional of the step): an
op with no ``op_name`` (XLA inserts copies without one), and an op that
XLA shares between several call sites, whose ``op_name`` joins theirs
(``fabric/hop/compact/fabric/inject/compact``): it takes the call site
under the op around it, else the commonest. An op whose ``op_name`` has no
``fabric`` segment (the scan's own bookkeeping) has no scope.

* ``scope_s``: leaf-op seconds by scope path, averaged over the devices
  that ran anything, on the same leaves and window as ``trace.reduce``'s
  ``op_s``;
* ``span_s``, ``span_n``: seconds and count of the program's host spans
  (``PROGRAM_SPAN``) by name, among the spans that start inside the window
  on the thread that ran it;
* ``idle_s``: the window's idle gaps (as ``trace.reduce`` finds them) by
  the innermost *program* span around their middle, or ``outside the
  program``; JAX's own spans inside a program span (``PjitFunction``,
  ``np.asarray``) do not take the gap from it.
"""
from __future__ import annotations

import bisect
import collections
import functools
import pathlib
import re
import sys
import time

if __name__ == "__main__":
    sys.path[:0] = [str(pathlib.Path(__file__).resolve().parent.parent / p)
                    for p in ("src", "")]

from bench import trace  # noqa: E402

SCOPE_WORDS = frozenset((
    "fabric", "activate", "inject", "hop", "missed", "stats", "finish",
    "lookup", "compact", "scatter_back", "enqueue", "backlog_filter",
    "admit", "reorder", "exchange"))
PROGRAM_SPAN = re.compile(r"^(OpenOpticsNet\.|run\.|ingest\.|advance\.|"
                          r"retrace/)")
OUTSIDE = "outside the program"
MODULES_LINE = "XLA Modules"
METADATA_PLANE = "/host:metadata"
HLO_STAT = "Hlo Proto"
# the per-layer metrics that read these keys (bench/metrics/<name>.py)
PROGRAM_METRICS = ("backlog_filter_share", "lookup_share", "admit_share",
                   "compact_share", "run_host_ms")


def scope_paths(op_name: str) -> list[str]:
    """The scope path of each call site an ``op_name`` names:
    ``["fabric/hop/admit"]`` for ``jit(f)/while/body/fabric/hop/cond/
    branch_1_fun/admit/sort``, one per ``fabric`` segment, none outside
    the fabric step."""
    out = []
    for seg in op_name.split("/"):
        if seg == "fabric":
            out.append([seg])
        elif out and seg in SCOPE_WORDS:
            out[-1].append(seg)
    return ["/".join(p) for p in out]


# -- the protocol buffers' wire format, for the two messages read here ------
# (XSpace: tsl/profiler/protobuf/xplane.proto; HloProto: xla/service/hlo.proto)

def _varint(buf, i):
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """(field number, value) of a serialised message: an int for a varint,
    a memoryview for a length-delimited field."""
    buf = memoryview(buf)
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif kind in (1, 5):
            value, i = None, i + (8 if kind == 1 else 4)
        else:
            raise ValueError(f"wire type {kind} in a profile")
        yield key >> 3, value


def _field(buf, number):
    return next((v for f, v in _fields(buf) if f == number), None)


def program_hlo(raw: bytes) -> dict:
    """``{program: serialised HloProto}`` of the metadata plane of the
    serialised XSpace ``raw`` (XSpace.planes 1; XPlane.name 2,
    .event_metadata 4, .stat_metadata 5; map entries key 1, value 2;
    XEventMetadata.name 2, .stats 5; XStat.metadata_id 1, .bytes_value 6;
    XStatMetadata.name 2)."""
    for fnum, plane in _fields(raw):
        name = bytes(_field(plane, 2) or b"").decode() if fnum == 1 else ""
        if name != METADATA_PLANE:
            continue
        entries = [(f, _field(v, 2)) for f, v in _fields(plane) if f in (4, 5)]
        stat = {_field(m, 1): bytes(_field(m, 2) or b"").decode()
                for f, m in entries if f == 5}
        out = {}
        for f, m in entries:
            if f != 4:
                continue
            for g, st in _fields(m):
                if g == 5 and stat.get(_field(st, 1)) == HLO_STAT:
                    out[bytes(_field(m, 2)).decode()] = _field(st, 6)
        return out
    return {}


def op_names(hlo) -> dict:
    """``{instruction: op_name}`` of a serialised HloProto (hlo_module 1;
    HloModuleProto.computations 3; HloComputationProto.instructions 2;
    HloInstructionProto.name 1, .metadata 7; OpMetadata.op_name 2)."""
    out = {}
    for comp in (v for f, v in _fields(_field(hlo, 1)) if f == 3):
        for ins in (v for f, v in _fields(comp) if f == 2):
            name = meta = None
            for f, v in _fields(ins):
                if f == 1:
                    name = bytes(v).decode()
                elif f == 7:
                    meta = _field(v, 2)
            if name is not None and meta is not None:
                out[name] = bytes(meta).decode()
    return out


def _read(path) -> bytes:
    import gzip
    path = pathlib.Path(path)
    data = path.read_bytes()
    return gzip.decompress(data) if path.suffix == ".gz" else data


def _leaf_scopes(ev, candidates):
    """Leaf ops of one device's window ``ev`` ((start, end, name), sorted
    by start and then longest first, as ``trace._leaves`` takes them) with
    their scope path, resolved from the ops around them (module doc)."""
    out, stack = [], []          # stack: (end, scope) of the ops around
    for k, (s, e, name) in enumerate(ev):
        while stack and stack[-1][0] <= s:
            stack.pop()
        paths, named = candidates(name)
        outer = next((sc for _, sc in reversed(stack) if sc), None)
        if len(set(paths)) > 1 and outer:
            under = [p for p in paths if p == outer or
                     p.startswith(outer + "/")]
            paths = under or paths
        if paths:
            scope = collections.Counter(paths).most_common(1)[0][0]
        else:
            scope = None if named else outer
        if k + 1 < len(ev) and ev[k + 1][0] < e and ev[k + 1][1] <= e:
            stack.append((e, scope))
        else:
            out.append((s, e, scope))
    return out


def _scopes(raw):
    """``scopes(program, event name)``: the event's scope paths in that
    program and whether its instruction has an ``op_name``. Each program's
    HLO is read once, each distinct event looked up once."""
    hlo = program_hlo(raw)
    names = {}

    @functools.cache
    def scopes(program, event_name):
        if program not in names:
            names[program] = op_names(hlo[program]) if program in hlo else {}
        op = names[program].get(event_name.split(" ")[0].lstrip("%"))
        return (scope_paths(op) if op else [], bool(op))

    return scopes


def reduce(path) -> dict:
    """``scope_s``, ``span_s``, ``span_n`` and ``idle_s`` of the window in
    the trace at ``path`` (see the module doc)."""
    import jax
    raw = _read(path)
    pd = jax.profiler.ProfileData.from_serialized_xspace(raw)
    host, devices, modules = [], {}, {}
    for plane in pd.planes:
        m = trace.DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name == trace.OPS_LINE:
                    devices[int(m.group(1))] = [
                        (e.start_ns, e.start_ns + e.duration_ns, e.name)
                        for e in line.events]
                elif line.name == MODULES_LINE:
                    modules[int(m.group(1))] = sorted(
                        (e.start_ns, e.start_ns + e.duration_ns, e.name)
                        for e in line.events)
        elif plane.name.startswith("/host"):
            for line in plane.lines:
                ev = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                      for e in line.events]
                if any(n == trace.WINDOW for _, _, n in ev):
                    host = ev
    windows = [(s, e) for s, e, n in host if n == trace.WINDOW]
    if not windows:
        raise ValueError(f"the trace has no host span named {trace.WINDOW!r}")
    w0, w1 = windows[-1]

    scopes = _scopes(raw)
    by_scope, used, first_busy = collections.Counter(), 0, None
    for dev in sorted(devices):
        ev = [(max(s, w0), min(e, w1), n) for s, e, n in devices[dev]
              if e > w0 and s < w1]
        if not ev:
            continue
        used += 1
        if first_busy is None:
            first_busy = trace._union((s, e) for s, e, _ in ev)
        ev.sort(key=lambda x: (x[0], -x[1]))
        starts = [x[0] for x in ev]
        for m0, m1, program in modules.get(dev, []):
            inside = ev[bisect.bisect_left(starts, m0):
                        bisect.bisect_left(starts, m1)]
            for s, e, scope in _leaf_scopes(
                    inside, functools.partial(scopes, program)):
                if scope is not None:
                    by_scope[scope] += e - s

    spans = sorted((s, e, n) for s, e, n in host
                   if w0 <= s < w1 and PROGRAM_SPAN.match(n))
    span_s, span_n = collections.Counter(), collections.Counter()
    for s, e, n in spans:
        span_s[n] += e - s
        span_n[n] += 1
    idle = collections.Counter()
    if first_busy is not None:
        starts = [s for s, _, _ in spans]
        edge = w0
        for s, e in first_busy + [[w1, w1]]:
            if s > edge:
                name = trace._covering(spans, starts, (edge + s) / 2)
                idle[OUTSIDE if name == "idle host" else name] += s - edge
            edge = max(edge, e)
    ns = 1e-9
    return dict(
        scope_s={k: v / max(used, 1) * ns for k, v in by_scope.items()},
        span_s={k: v * ns for k, v in span_s.items()},
        span_n=dict(span_n),
        idle_s={k: v * ns for k, v in idle.items()},
    )


def scope_share(red: dict, *scopes: str) -> float | None:
    """Share of busy time, in %, in the given scopes and every scope nested
    in them; ``None`` where the trace has no scope or no such op ran."""
    scope_s = red.get("scope_s")
    if not scope_s or not red.get("busy_s"):
        return None
    t = sum(v for k, v in scope_s.items()
            if any(k == s or k.startswith(s + "/") for s in scopes))
    return 100.0 * t / red["busy_s"] if t > 0 else None


def main(argv=None) -> int:
    """One traced run of a cell, reduced by both reducers (module doc)."""
    import json
    import shutil
    import tempfile

    import jax

    from bench import harness, run
    args = run.parse(list(argv if argv is not None else sys.argv[1:])
                     + ["--trace", "1"])
    spec = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    cell = harness.find_cell(spec, args.workload)
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < int(cell["chips"]):
        print(f"program_trace: cell {args.workload} needs {cell['chips']} "
              f"TPU chip(s)", file=sys.stderr)
        return 3
    run.use_compile_cache()
    log_dir = tempfile.mkdtemp(prefix="bench_trace_")
    try:
        result = run.run(args, spec, trace_dir=log_dir)
        t0 = time.perf_counter()
        red = reduce(trace.newest_xplane(log_dir))
        reduce_s = time.perf_counter() - t0
    finally:
        shutil.rmtree(log_dir, ignore_errors=True)
    device = result["device"]
    ctx = run.Context(spans=None, window=(0.0, 0.0), slices=0, setup={},
                      trace={"busy_s": device["busy_s"], **red})
    metrics = {name: harness.load_module("metrics", name).read(ctx)
               for name in PROGRAM_METRICS}
    dep = harness.load_json("configs", cell["config"])
    mix = harness.load_json("traffic", cell["traffic"])
    slices = result["attempted"] * mix["num_slices"]
    print(json.dumps({
        "result": result, "program_metrics": metrics, "program_trace": red,
        "traced_pkt_slices_per_s": dep["packets"] * slices
        / device["window_s"],
        "program_reduce_s": reduce_s}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
