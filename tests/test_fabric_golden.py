"""Golden regression for the re-architected fabric hot path (ISSUE 1): the
incremental-occupancy / cond-skipping / fused-lookup ``simulate`` must produce
bit-identical ``SimResult`` outputs to the reference formulation
(``tests/fabric_ref.py``, the seed data plane) across the §5.2 mechanism
matrix, plus a determinism check and the Pallas lookup path.
"""
import dataclasses

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import (FabricConfig, FabricTables, Workload, hoho,
                        round_robin, simulate, synthesize, ucmp, vlb)
from repro.kernels import ops

from fabric_ref import simulate_ref

N = 8
SLICES = 48


def _workload():
    return synthesize("rpc", N, 24, slice_bytes=4_000, load=0.9,
                      max_packets=420, seed=11)


def _tables(alg=ucmp):
    sched = round_robin(N, 1)
    return FabricTables.build(sched, alg(sched))


def _assert_results_equal(a, b):
    for f in dataclasses.fields(a):
        np.testing.assert_array_equal(
            getattr(a, f.name), getattr(b, f.name), err_msg=f.name)


CFG_MATRIX = [
    dict(cc_detect=cc, pushback=pb, offload=off)
    for cc in (False, True) for pb in (False, True) for off in (False, True)
    # push-back builds on congestion detection (paper §5.2)
    if not (pb and not cc)
]


@pytest.mark.parametrize("over", CFG_MATRIX,
                         ids=lambda o: "-".join(f"{k}={int(v)}" for k, v in o.items()))
def test_simulate_bit_identical_to_reference(over):
    wl = _workload()
    tables = _tables()
    cfg = FabricConfig(slice_bytes=4_000, offload_horizon=1,
                       switch_buffer=30_000, **over)
    _assert_results_equal(simulate(tables, wl, cfg, SLICES),
                          simulate_ref(tables, wl, cfg, SLICES))


def test_simulate_bit_identical_flow_pausing():
    wl = _workload()
    tables = _tables(vlb)
    cfg = FabricConfig(slice_bytes=4_000, flow_pausing=True)
    _assert_results_equal(simulate(tables, wl, cfg, SLICES),
                          simulate_ref(tables, wl, cfg, SLICES))


def test_simulate_bit_identical_rotor_single_hop():
    wl = _workload()
    tables = _tables(hoho)
    cfg = FabricConfig(slice_bytes=4_000, hops_per_slice=1)
    _assert_results_equal(simulate(tables, wl, cfg, SLICES),
                          simulate_ref(tables, wl, cfg, SLICES))


@pytest.mark.parametrize("over,gate", [
    # 4x overload: the backlog gate opens at hops >= 1 in every slice
    (dict(), "open"),  # backlog-filter + tiered compact views, plain cc_detect
    (dict(pushback=True, offload=True, offload_horizon=1,
          switch_buffer=200_000), "open"),
    # 0.4x: a few slices reject, so the gate opens at hops >= 1 in those
    # alone; UCMP's multi-hop candidates reach hop 1 in the others too
    (dict(slice_bytes=400_000), "some"),
    (dict(slice_bytes=400_000, pushback=True), "some"),
    # 0.2x: no rejection all run, so the gate stays shut at every hop
    (dict(slice_bytes=800_000), "shut"),
    (dict(slice_bytes=800_000, pushback=True), "shut"),
], ids=["plain", "pushback-offload", "light", "light-pushback", "gate-shut",
        "gate-shut-pushback"])
def test_simulate_bit_identical_large_population(over, gate):
    """P > the compact-view tier bounds, so the tiered compact/full dispatch
    (including spill to the full-width path) is exercised, with the backlog
    gate open in every slice, in some, and in none."""
    import repro.core.fabric as fabric
    assert fabric.SMALL_C < 9000 < fabric.ADMIT_C + 1000
    wl = synthesize("rpc", N, 12, slice_bytes=40_000, load=4.0,
                    max_packets=9000, seed=13)
    assert wl.num_packets > fabric.SMALL_C
    tables = _tables()
    cfg = FabricConfig(**{"slice_bytes": 40_000, **over})
    got = simulate(tables, wl, cfg, 20)
    _assert_results_equal(got, simulate_ref(tables, wl, cfg, 20))
    # a rejection misses its slice, and only a rejection opens the gate
    rejecting = int((got.slice_miss > 0).sum())
    assert {"open": rejecting > 0, "some": 0 < rejecting < 20,
            "shut": rejecting == 0}[gate]


def test_simulate_bit_identical_mixed_rx_capacity_pressure():
    """Push-back's rejected-prefix backlog cut under *mixed* admission
    groups: a small switch buffer makes rx admission bind (rx-subject
    buffered hops) in the same sort groups where a hybrid electrical share
    and 2x load make the capacity prefix bind. The cut must stay
    semantically invisible — only packets with no rescuable rx-subject
    predecessor may be filtered — so the fabric stays bit-identical to the
    unfiltered reference."""
    wl = synthesize("rpc", N, 24, slice_bytes=3_000, load=2.0,
                    max_packets=1200, seed=7)
    tables = _tables()
    cfg = FabricConfig(slice_bytes=3_000, elec_bytes=1_500, cc_detect=True,
                       pushback=True, switch_buffer=9_000)
    _assert_results_equal(simulate(tables, wl, cfg, SLICES),
                          simulate_ref(tables, wl, cfg, SLICES))


def test_simulate_deterministic():
    wl = _workload()
    tables = _tables()
    cfg = FabricConfig(slice_bytes=4_000, pushback=True, offload=True,
                       offload_horizon=1)
    _assert_results_equal(simulate(tables, wl, cfg, SLICES),
                          simulate(tables, wl, cfg, SLICES))


def test_simulate_pallas_lookup_path_matches():
    """The Pallas time-flow-lookup kernel wired in as the fabric lookup op
    (interpret mode on CPU) is bit-identical to the jnp gather path."""
    wl = _workload()
    tables = _tables()
    base = FabricConfig(slice_bytes=4_000)
    pal = dataclasses.replace(base, lookup_impl="pallas-interpret")
    _assert_results_equal(simulate(tables, wl, base, 12),
                          simulate(tables, wl, pal, 12))


def test_time_flow_lookup_pads_arbitrary_packet_counts():
    """P not a multiple of the block size works (pad + slice)."""
    rng = np.random.default_rng(3)
    n, k = 10, 4
    tbl_n = np.full((n, n, k), -1, np.int32)
    nv = rng.integers(0, k + 1, size=(n, n))
    for i in range(n):
        for jj in range(n):
            tbl_n[i, jj, :nv[i, jj]] = rng.integers(0, n, nv[i, jj])
    tbl_d = rng.integers(0, 6, size=(n, n, k)).astype(np.int32) * (tbl_n >= 0)
    for P in (1, 7, 255, 1000, 1025):
        node = jnp.asarray(rng.integers(0, n, P), jnp.int32)
        dst = jnp.asarray(rng.integers(0, n, P), jnp.int32)
        h = jnp.asarray(rng.integers(0, 2 ** 31, P), jnp.uint32)
        an, ad = ops.time_flow_lookup(jnp.asarray(tbl_n), jnp.asarray(tbl_d),
                                      node, dst, h, bp=256, interpret=True)
        bn, bd = ops.time_flow_lookup(jnp.asarray(tbl_n), jnp.asarray(tbl_d),
                                      node, dst, h, impl="ref")
        assert an.shape == (P,) and ad.shape == (P,)
        np.testing.assert_array_equal(np.asarray(an), np.asarray(bn))
        np.testing.assert_array_equal(np.asarray(ad), np.asarray(bd))
