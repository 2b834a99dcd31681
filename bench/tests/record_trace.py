"""Record the small chip traces that ``test_bench_trace.py`` reduces.

    python3 bench/tests/record_trace.py                # on a TPU host
    python3 bench/tests/record_trace.py --trim <cell> <trace.xplane.pb[.gz]>

The first form runs ``vlb_kv_run`` and ``vlb_kv_service`` at the tests'
tiny size with ``--trace 1`` and trims each window's trace; the second
trims a trace recorded before. Trimming keeps the first ``CALLS`` calls of
the window: the host thread that ran it, the device's ``XLA Ops`` line,
and the names of the ops, with every statistic and every other plane and
line left out; the ``window`` span is cut to end with the last call kept.
The result is ``bench/tests/data/<cell>.xplane.pb.gz``.

Reading and writing the trace's protocol buffer needs TensorFlow's copy
of its schema (``tensorflow.tsl.profiler.protobuf.xplane_pb2``); the tests
read the trimmed file with JAX alone.
"""
from __future__ import annotations

import gzip
import pathlib
import sys
import tempfile

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

DATA = pathlib.Path(__file__).resolve().parent / "data"
CELLS = ("vlb_kv_run", "vlb_kv_service")
CALLS = 2                  # calls of the window a trimmed trace keeps
SPAN = {"vlb_kv_run": "run", "vlb_kv_service": "service_result"}


def trim(raw: bytes, last_span: str) -> bytes:
    """The trimmed, serialised XSpace of the serialised trace ``raw``."""
    from tensorflow.tsl.profiler.protobuf import xplane_pb2
    xs = xplane_pb2.XSpace()
    xs.ParseFromString(raw)
    host = next(p for p in xs.planes if p.name == "/host:CPU")
    dev = next(p for p in xs.planes if p.name == "/device:TPU:0")
    name = {k: m.name for k, m in host.event_metadata.items()}
    line = next(ln for ln in host.lines
                if any(name[e.metadata_id] == "window" for e in ln.events))
    window = next(e for e in line.events if name[e.metadata_id] == "window")
    base = line.timestamp_ns * 1000                    # picoseconds
    w0 = base + window.offset_ps
    ends = sorted(base + e.offset_ps + e.duration_ps for e in line.events
                  if name[e.metadata_id] == last_span
                  and base + e.offset_ps >= w0)
    w1 = ends[CALLS - 1]
    window.duration_ps = w1 - w0

    out = xplane_pb2.XSpace()
    for plane, keep_line in ((host, lambda ln: ln is line),
                             (dev, lambda ln: ln.name == "XLA Ops")):
        new = out.planes.add(id=plane.id, name=plane.name)
        used = set()
        for ln in plane.lines:
            if not keep_line(ln):
                continue
            nl = new.lines.add(id=ln.id, display_id=ln.display_id,
                               name=ln.name, display_name=ln.display_name,
                               timestamp_ns=ln.timestamp_ns)
            b = ln.timestamp_ns * 1000
            for e in ln.events:
                s = b + e.offset_ps
                if s < w1 and s + e.duration_ps > w0:
                    nl.events.add(metadata_id=e.metadata_id,
                                  offset_ps=e.offset_ps,
                                  duration_ps=e.duration_ps)
                    used.add(e.metadata_id)
        for k in used:
            m = plane.event_metadata[k]
            new.event_metadata[k].CopyFrom(xplane_pb2.XEventMetadata(
                id=m.id, name=m.name, display_name=m.display_name))
    return out.SerializeToString()


def record(cell: str) -> bytes:
    """One tiny ``--trace 1`` run of ``cell``; its trace, serialised."""
    from bench import trace
    from bench.tests import tiny
    with tempfile.TemporaryDirectory() as d:
        tiny.run_cell(cell, seconds=0.5, trace=1, trace_dir=d)
        return trace.newest_xplane(d).read_bytes()


def main(argv: list[str]) -> int:
    if argv[:1] == ["--trim"]:
        cell, path = argv[1], pathlib.Path(argv[2])
        raws = {cell: gzip.decompress(path.read_bytes())
                if path.suffix == ".gz" else path.read_bytes()}
    else:
        import jax
        if jax.devices()[0].platform != "tpu":
            print("record_trace: needs a TPU", file=sys.stderr)
            return 3
        from bench.tests import tiny
        tiny.shrink()
        raws = {cell: record(cell) for cell in CELLS}
    for cell, raw in raws.items():
        small = gzip.compress(trim(raw, SPAN[cell]), mtime=0)
        (DATA / f"{cell}.xplane.pb.gz").write_bytes(small)
        print(cell, len(small), "bytes")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
