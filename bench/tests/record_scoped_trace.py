"""Record the small chip traces with the program's own names that
``test_program_trace.py`` reduces.

    python3 bench/tests/record_scoped_trace.py                # on a TPU host
    python3 bench/tests/record_scoped_trace.py --trim <cell> <trace.xplane.pb[.gz]>

The first form runs ``vlb_kv_run`` and ``ucmp_kv_run`` with ``--trace 1``
at the tests' tiny size, with 4,096 packets at 10% load in place of 2,048
at 5% (so that the fabric's compact views, which start at 2,048 packets,
exist, and the packets still arrive over about 15 slices), and trims each
window's trace; the second trims a trace recorded before. Trimming is
``record_trace.trim`` (the first two calls of the window, the host thread
that ran it with every span, program spans included, and the device's
``XLA Ops`` line) and keeps what ``bench/program_trace.py`` reads besides:
the device's ``XLA Modules`` line in the same stretch, and the
``/host:metadata`` plane with each program's HLO cut to the name and
``op_name`` of each instruction that a kept op event names. The result is
``bench/tests/data/<cell>.scoped.xplane.pb.gz``.
"""
from __future__ import annotations

import gzip
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from bench.program_trace import (HLO_STAT, METADATA_PLANE,  # noqa: E402
                                 MODULES_LINE)
from bench.tests import record_trace  # noqa: E402

CELLS = ("vlb_kv_run", "ucmp_kv_run")
PACKETS, LOAD = 4096, 0.1


def trim(raw: bytes) -> bytes:
    """``record_trace.trim`` of the serialised trace ``raw``, with the
    module line and the programs' instruction names (module doc)."""
    from tensorflow.compiler.xla.service import hlo_pb2
    from tensorflow.tsl.profiler.protobuf import xplane_pb2
    full = xplane_pb2.XSpace()
    full.ParseFromString(raw)
    out = xplane_pb2.XSpace()
    out.ParseFromString(record_trace.trim(raw, "run"))
    src = next(p for p in full.planes if p.name == "/device:TPU:0")
    dst = next(p for p in out.planes if p.name == "/device:TPU:0")
    ops = next(ln for ln in dst.lines if ln.name == "XLA Ops")
    base = ops.timestamp_ns * 1000
    t0 = min(base + e.offset_ps for e in ops.events)
    t1 = max(base + e.offset_ps + e.duration_ps for e in ops.events)
    modules = next(ln for ln in src.lines if ln.name == MODULES_LINE)
    keep = dst.lines.add(id=modules.id, display_id=modules.display_id,
                         name=modules.name,
                         display_name=modules.display_name,
                         timestamp_ns=modules.timestamp_ns)
    b = modules.timestamp_ns * 1000
    for e in modules.events:
        if b + e.offset_ps < t1 and b + e.offset_ps + e.duration_ps > t0:
            keep.events.add(metadata_id=e.metadata_id, offset_ps=e.offset_ps,
                            duration_ps=e.duration_ps)
            m = src.event_metadata[e.metadata_id]
            dst.event_metadata[e.metadata_id].CopyFrom(
                xplane_pb2.XEventMetadata(id=m.id, name=m.name))
    named = {dst.event_metadata[e.metadata_id].name.split(" ")[0].lstrip("%")
             for e in ops.events}
    meta = next(p for p in full.planes if p.name == METADATA_PLANE)
    new = out.planes.add(id=meta.id, name=meta.name)
    stat = next(k for k, m in meta.stat_metadata.items()
                if m.name == HLO_STAT)
    new.stat_metadata[stat].CopyFrom(meta.stat_metadata[stat])
    for k, m in meta.event_metadata.items():
        hlo = hlo_pb2.HloProto()
        hlo.ParseFromString(next(st.bytes_value for st in m.stats
                                 if st.metadata_id == stat))
        cut = hlo_pb2.HloProto()
        cut.hlo_module.name = hlo.hlo_module.name
        comp = cut.hlo_module.computations.add(name="instructions")
        for c in hlo.hlo_module.computations:
            for ins in c.instructions:
                if ins.name in named and ins.metadata.op_name:
                    comp.instructions.add(name=ins.name).metadata.op_name = \
                        ins.metadata.op_name
        em = new.event_metadata[k]
        em.CopyFrom(xplane_pb2.XEventMetadata(id=m.id, name=m.name))
        em.stats.add(metadata_id=stat,
                     bytes_value=cut.SerializeToString())
    return out.SerializeToString()


def shrink():
    """The tests' tiny sizes, with ``PACKETS`` packets at ``LOAD``."""
    from bench import harness
    from bench.tests import tiny
    tiny.shrink()
    small = harness.load_json

    def sized(kind, name):
        d = small(kind, name)
        d.update(packets=PACKETS) if kind == "configs" else d.update(load=LOAD)
        return d

    harness.load_json = sized


def main(argv: list[str]) -> int:
    if argv[:1] == ["--trim"]:
        cell, path = argv[1], pathlib.Path(argv[2])
        raws = {cell: gzip.decompress(path.read_bytes())
                if path.suffix == ".gz" else path.read_bytes()}
    else:
        import jax
        if jax.devices()[0].platform != "tpu":
            print("record_scoped_trace: needs a TPU", file=sys.stderr)
            return 3
        shrink()
        raws = {cell: record_trace.record(cell) for cell in CELLS}
    for cell, raw in raws.items():
        small = gzip.compress(trim(raw), mtime=0)
        (record_trace.DATA / f"{cell}.scoped.xplane.pb.gz").write_bytes(small)
        print(cell, len(small), "bytes")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
