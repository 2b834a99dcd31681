"""Program tracing (``repro.core.tracing``): the fabric step's device scopes
in the lowered programs, the user API's host spans in a profile, and the
retrace counter of the jitted entry points."""
import pathlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (FabricConfig, FabricTables, OpenOpticsNet,
                        round_robin, vlb)
from repro.core import fabric, tracing
from repro.core.fabric import Workload
from repro.core.telemetry import TelemetryConfig
from repro.distributed import sharding as dshard

import hlo_text

N = 8
SLICES = 16
# above the smallest compact view (2,048), so the compacted phases exist
P = 4096
WORDS = {w for s in tracing.SCOPES for w in s.split("/")} | {tracing.EXCHANGE}
NO_VIEWS = {"fabric/inject/compact", "fabric/inject/scatter_back",
            "fabric/hop/compact", "fabric/hop/scatter_back"}


def _workload(seed=0, packets=P, slices=12):
    rng = np.random.default_rng(seed)
    src = rng.integers(0, N, packets).astype(np.int32)
    dst = ((src + 1 + rng.integers(0, N - 1, packets)) % N).astype(np.int32)
    return Workload(src=src, dst=dst,
                    size=rng.integers(64, 1500, packets).astype(np.int32),
                    t_inject=np.sort(rng.integers(0, slices, packets)
                                     ).astype(np.int32),
                    flow=np.arange(packets, dtype=np.int32),
                    seq=np.zeros(packets, np.int32),
                    is_eleph=np.zeros(packets, bool))


def _net():
    net = OpenOpticsNet(dict(node="rack", node_num=N, uplink=1,
                             fabric=dict(slice_bytes=12_000)))
    sched = round_robin(N, 1)
    net.deploy_topo(sched)
    net.deploy_routing(vlb(sched))
    return net


def _device_inputs(wl):
    sched = round_robin(N, 1)
    t = FabricTables.build(sched, vlb(sched))
    dev = lambda a, dt=jnp.int32: jnp.asarray(a, dt)
    return dict(conn=dev(t.conn), tf_next=dev(t.tf_next), tf_dep=dev(t.tf_dep),
                inj_next=dev(t.inj_next), inj_dep=dev(t.inj_dep),
                first_direct=dev(t.first_direct), src=dev(wl.src),
                dst=dev(wl.dst), size=dev(wl.size),
                t_inject=dev(wl.t_inject), flow=dev(wl.flow),
                seq=dev(wl.seq), is_eleph=dev(wl.is_eleph, jnp.bool_))


def _lowered(program, tele):
    cfg = FabricConfig(slice_bytes=12_000)
    j = _device_inputs(_workload())
    if program == "_simulate_jit":
        return fabric._simulate_jit.lower(j, cfg, SLICES, True, P, tele)
    if program == "_window_jit":
        state = fabric._init_state(j, P, tele)
        return fabric._window_jit.lower(j, state, jnp.int32(0), cfg, SLICES,
                                        True, P, tele)
    mesh, d = dshard.fabric_mesh(4)
    return fabric._simulate_sharded_jit.lower(j, cfg, SLICES, True, P, d,
                                              mesh, tele)


def _scope_paths(lowered):
    """The scope path of every op that has one. An op's location name is
    the ``op_name`` metadata of its HLO instruction; its scope is the
    segments from ``fabric`` on that are names of ``tracing``'s tree."""
    paths = set()
    text = lowered.as_text(debug_info=True)
    for name in re.findall(r'loc\("([^"]*)"', text):
        seg = name.split("/")
        if "fabric" in seg:
            seg = seg[seg.index("fabric"):]
            paths.add("/".join(w for w in seg if w in WORDS))
    return paths


@pytest.mark.parametrize("tele", [None, TelemetryConfig()],
                         ids=["telemetry_off", "telemetry_on"])
@pytest.mark.parametrize("program", ["_simulate_jit", "_window_jit",
                                     "_simulate_sharded_jit"])
def test_lowered_programs_carry_every_scope(program, tele):
    paths = _scope_paths(_lowered(program, tele))
    sharded = program == "_simulate_sharded_jit"
    exchange = {p for p in paths if p.endswith("/" + tracing.EXCHANGE)}
    assert bool(exchange) == sharded
    found = {p.rsplit("/" + tracing.EXCHANGE, 1)[0] for p in paths}
    want = {"fabric"} | set(tracing.SCOPES)
    if sharded:
        # shard_map runs every phase at full width: no compact views
        want -= NO_VIEWS
    elif tele is None:
        # without telemetry the result is the scan's own output: no op
        want.discard("fabric/finish")
    assert found == want


@pytest.mark.parametrize("pushback,hops,per_hop", [
    (False, 4, 1),     # the group cut
    (True, 4, 2),      # the group cut and the receiver cut
    (False, 1, 0),     # hop 0 alone reads no cut
], ids=["pushback_off", "pushback_on", "one_hop"])
def test_backlog_gather_only_in_a_cond_from_hop_one(pushback, hops, per_hop):
    """The backlog filter gathers the cuts only under its ``gather``
    scope, inside a cond branch of its own at each hop after the first:
    hop 0 has no gather, and no gather of the filter runs unconditionally."""
    cfg = FabricConfig(slice_bytes=12_000, cc_detect=True, pushback=pushback,
                       hops_per_slice=hops)
    lowered = fabric._simulate_jit.lower(_device_inputs(_workload()), cfg,
                                         SLICES, True, P, None)
    found = hlo_text.scoped_gathers(
        lowered.as_text(dialect="hlo", debug_info=True),
        WORDS | {"gather"})
    gated = [b for path, b in found if path == tracing.BACKLOG_GATHER]
    assert len(gated) == per_hop * (hops - 1)
    assert all(gated) and len(set(gated)) == hops - 1
    assert not [p for p, _ in found if p == "fabric/hop/backlog_filter"]


def _host_events(log_dir):
    """Every host event of the newest profile under ``log_dir``, as
    (start, end, name) per thread."""
    path = sorted(pathlib.Path(log_dir).rglob("*.xplane.pb"))[-1]
    pd = jax.profiler.ProfileData.from_file(str(path))
    return [[(e.start_ns, e.end_ns, e.name) for e in line.events]
            for plane in pd.planes if plane.name.startswith("/host")
            for line in plane.lines]


def test_run_spans_in_a_profile(tmp_path):
    net = _net()
    wls = [_workload(seed=s, packets=256, slices=6) for s in (1, 2)]
    # a slice count no other test uses, so the first call traces inside
    # the profile
    with jax.profiler.trace(str(tmp_path)):
        for wl in wls:
            net.run(wl, 11)
    line = next(ev for ev in _host_events(tmp_path)
                if any(n == "OpenOpticsNet.run" for _, _, n in ev))
    runs = [(s, e) for s, e, n in line if n == "OpenOpticsNet.run"]
    assert len(runs) == len(wls)
    for name in ("run.tables", "run.to_device", "run.dispatch",
                 "run.device_wait", "run.result_copy", "run.traffic_matrix"):
        inner = [(s, e) for s, e, n in line if n == name]
        assert len(inner) == len(wls), name
        for (s, e), (rs, re_) in zip(sorted(inner), sorted(runs)):
            assert rs <= s and e <= re_, name
    retraced = [(s, e) for s, e, n in line if n == "retrace/_simulate_jit"]
    assert len(retraced) == 1 and runs[0][0] <= retraced[0][0] < runs[0][1]


def test_same_shapes_do_not_retrace():
    net = _net()
    net.run(_workload(seed=3, packets=256, slices=6), 10)
    before = tracing.retraces.copy()
    res = net.run(_workload(seed=4, packets=256, slices=6), 10)
    assert tracing.retraces == before
    assert (res.t_deliver >= 0).any()


def test_growing_ingest_retraces_the_window_once():
    net = _net()
    net.ingest(_workload(seed=5, packets=128, slices=4))
    net.advance(6)
    net.advance(6)
    before = tracing.retraces["_window_jit"]
    net.ingest(_workload(seed=6, packets=64, slices=4))
    net.advance(6)
    net.advance(6)
    assert tracing.retraces["_window_jit"] == before + 1
    assert net.snapshot()["packets"]["total"] == 192
