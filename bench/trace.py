"""Reduction of a profiler trace of the measured window to the numbers the
per-layer metrics and the ``breakdown`` read.

The trace is JAX's ``.xplane.pb``, read with ``jax.profiler.ProfileData``.
Device planes are ``/device:TPU:<n>``; their ``XLA Ops`` line holds one
event per HLO operation executed, named by the operation's HLO text
(``%sort.8 = (s32[8192]{...}, ...) sort(...), dimensions={0},
is_stable=true, ...``). Loops and conditionals appear there too, around
the ops of their bodies; async copies sit on another line and are not
read. The host's planes hold the benchmark's own spans
(``jax.profiler.TraceAnnotation``) and JAX's, on the host clock that the
profiler also puts the device events on. The window is the span named
``window``.

* busy: the union of a device's op intervals inside the window, averaged
  over the devices that ran anything;
* op time: the time of leaf ops (an op that encloses other ops, such as a
  loop, is left out so nothing counts twice), summed by op name and by
  opcode, averaged over devices;
* idle gaps: the stretches of the window in which the first device ran
  nothing, each put under the innermost host span that covers its middle.
"""
from __future__ import annotations

import bisect
import collections
import pathlib
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
WINDOW = "window"
# `%name = type opcode(` in an HLO instruction's text
_OPCODE = re.compile(r"=\s*(?:\([^=]*?\)|\S+)\s+([a-z][a-z0-9\-]*)\(")


def newest_xplane(log_dir) -> pathlib.Path:
    files = sorted(pathlib.Path(log_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return files[-1]


def opcode(name: str) -> str:
    """The HLO opcode of an op event named by its HLO text, else the stem of
    a bare name (``sort.12`` -> ``sort``)."""
    m = _OPCODE.search(name)
    if m:
        return m.group(1)
    return name.split(".")[0].lstrip("%")


def short(name: str, width: int = 160) -> str:
    """An op's HLO text cut to ``width`` characters, for the breakdown."""
    return name if len(name) <= width else name[:width - 3] + "..."


def _union(intervals):
    """Merged, sorted, disjoint (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _leaves(events):
    """Events that enclose no other event of the line."""
    ev = sorted(events, key=lambda x: (x[0], -x[1]))
    keep = []
    for k, (s, e, name) in enumerate(ev):
        if k + 1 < len(ev) and ev[k + 1][0] < e and ev[k + 1][1] <= e:
            continue
        keep.append((s, e, name))
    return keep


def load(path):
    """A trace file as ``jax.profiler.ProfileData`` (``.xplane.pb``, or the
    same gzipped)."""
    import gzip

    import jax
    path = pathlib.Path(path)
    if path.suffix == ".gz":
        return jax.profiler.ProfileData.from_serialized_xspace(
            gzip.decompress(path.read_bytes()))
    return jax.profiler.ProfileData.from_file(str(path))


def reduce(path) -> dict:
    """Numbers of the window in the trace at ``path`` (see module doc)."""
    pd = load(path)
    host, devices = [], {}
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[int(m.group(1))] = [
                        (e.start_ns, e.start_ns + e.duration_ns, e.name)
                        for e in line.events]
        elif plane.name.startswith("/host"):
            for line in plane.lines:
                ev = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                      for e in line.events]
                if any(n == WINDOW for _, _, n in ev):
                    host = ev          # the thread that ran the window
    windows = [(s, e) for s, e, n in host if n == WINDOW]
    if not windows:
        raise ValueError(f"the trace has no host span named {WINDOW!r}")
    w0, w1 = windows[-1]
    busy, by_name, by_code, first_busy = [], collections.Counter(), \
        collections.Counter(), None
    used = 0
    for dev in sorted(devices):
        ev = [(max(s, w0), min(e, w1), n) for s, e, n in devices[dev]
              if e > w0 and s < w1]
        if not ev:
            continue
        used += 1
        merged = _union((s, e) for s, e, _ in ev)
        busy.append(sum(e - s for s, e in merged))
        if first_busy is None:
            first_busy = merged
        for s, e, n in _leaves(ev):
            by_name[n] += e - s
    for n, t in by_name.items():
        by_code[opcode(n)] += t
    if not used:
        raise ValueError("no device op ran inside the window")
    spans = sorted((s, e, n) for s, e, n in host if s < w1 and e > w0
                   and n != WINDOW)
    starts = [s for s, _, _ in spans]
    gaps = collections.Counter()
    edge = w0
    for s, e in first_busy + [[w1, w1]]:
        if s > edge:
            gaps[_covering(spans, starts, (edge + s) / 2)] += s - edge
        edge = max(edge, e)
    ns = 1e-9
    return dict(
        window_s=(w1 - w0) * ns,
        busy_s=sum(busy) / used * ns,
        devices=used,
        op_s={k: v / used * ns for k, v in by_name.items()},
        opcode_s={k: v / used * ns for k, v in by_code.items()},
        idle_s={k: v * ns for k, v in gaps.items()},
    )


def _covering(spans, starts, t) -> str:
    """The innermost span of one host thread around time ``t`` (spans of a
    thread nest, so it is the covering span that started last), or
    ``idle host``."""
    for k in range(bisect.bisect_right(starts, t) - 1, -1, -1):
        if spans[k][1] >= t:
            return spans[k][2]
    return "idle host"


def breakdown(red: dict, top: int = 10) -> dict:
    """The trace's ``breakdown``: the device ops that took most time and
    the idle time by what the host was doing, each at most ``top``."""
    ops = sorted(red["op_s"].items(), key=lambda kv: -kv[1])[:top]
    idle = sorted(red["idle_s"].items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[short(k), v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in idle]}
