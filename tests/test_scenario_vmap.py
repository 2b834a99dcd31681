"""vmap parity suite (ISSUE 7): the vmapped scenario fleets
(``simulate_fleet`` / ``reconfigure_fleet``) must be **bit-identical** to the
per-scenario Python loop of jit calls they replace — fig8-style traffic-seed
sweeps, failover failure-trace sweeps, and reconfigure sweeps including every
``ReconfigResult`` history field (install/heal machinery intact under vmap).
"""
import dataclasses

import numpy as np
import pytest

from repro.core import (FabricConfig, FabricTables, ReconfigConfig,
                        TelemetryConfig, round_robin, simulate, simulate_fleet,
                        reconfigure, reconfigure_fleet, synthesize, ucmp, hoho,
                        random_trace, compile_masks, random_control_trace,
                        compile_control, toolkit)

N = 8
SLICES = 48


def _wl(seed):
    return synthesize("rpc", N, 24, slice_bytes=4_000, load=0.9,
                      max_packets=420, seed=seed)


def _assert_results_equal(a, b, where=""):
    for f in dataclasses.fields(a):
        if f.name == "telemetry":
            _assert_tele_equal(getattr(a, f.name), getattr(b, f.name), where)
            continue
        np.testing.assert_array_equal(getattr(a, f.name), getattr(b, f.name),
                                      err_msg=f"{where}{f.name}")


def _assert_tele_equal(a, b, where=""):
    assert (a is None) == (b is None), f"{where}telemetry presence"
    if a is None:
        return
    assert a.lat_edges == b.lat_edges
    for f in dataclasses.fields(a):
        if f.name == "lat_edges":
            continue
        np.testing.assert_array_equal(getattr(a, f.name), getattr(b, f.name),
                                      err_msg=f"{where}telemetry.{f.name}")


def test_fleet_seed_sweep_bit_identical():
    """fig8-style sweep: same tables/config, 6 traffic seeds — one batched
    program equals 6 jit calls, field for field."""
    sched = round_robin(N, 1)
    tables = FabricTables.build(sched, ucmp(sched))
    cfg = FabricConfig(slice_bytes=4_000, switch_buffer=30_000,
                       cc_detect=True, pushback=True)
    wls = [_wl(s) for s in range(6)]
    gots = simulate_fleet(tables, wls, cfg, SLICES)
    for i, (wl, got) in enumerate(zip(wls, gots)):
        _assert_results_equal(got, simulate(tables, wl, cfg, SLICES),
                              f"seed {i}: ")


@pytest.mark.parametrize("pushback", [False, True],
                         ids=["pushback_off", "pushback_on"])
@pytest.mark.parametrize("slice_bytes,shut", [(20_000, True), (4_000, False)],
                         ids=["gate-shut", "gate-open"])
def test_fleet_backlog_gate_bit_identical(slice_bytes, shut, pushback):
    """UCMP multi-hop under the backlog filter's gate, shut all run (no
    rejection on 20 KB circuits) and open at hops >= 1 (4 KB circuits):
    batched, the gate's cond runs both branches behind a select."""
    sched = round_robin(N, 1)
    tables = FabricTables.build(sched, ucmp(sched))
    cfg = FabricConfig(slice_bytes=slice_bytes, cc_detect=True,
                       pushback=pushback)
    wls = [_wl(s) for s in range(3)]
    gots = simulate_fleet(tables, wls, cfg, SLICES)
    for i, (wl, got) in enumerate(zip(wls, gots)):
        ref = simulate(tables, wl, cfg, SLICES)
        _assert_results_equal(got, ref, f"seed {i}: ")
        # a rejection misses its slice, and only a rejection opens the gate
        assert (ref.slice_miss.sum() == 0) == shut, f"seed {i}"


def test_fleet_failure_trace_sweep_bit_identical():
    """Failover sweep: one workload, 4 seeded failure traces (+ control
    faults), batched over the mask tensors."""
    sched = round_robin(N, 1)
    tables = FabricTables.build(sched, ucmp(sched))
    cfg = FabricConfig(slice_bytes=4_000, cc_detect=True)
    wl = _wl(0)
    fms = [compile_masks(random_trace(s, sched, SLICES, n_events=4), sched,
                         SLICES) for s in range(4)]
    cms = [compile_control(random_control_trace(s, N, SLICES, n_events=3),
                           SLICES, N) for s in range(4)]
    gots = simulate_fleet(tables, [wl] * 4, cfg, SLICES, failures=fms,
                          control=cms)
    for i, got in enumerate(gots):
        _assert_results_equal(
            got, simulate(tables, wl, cfg, SLICES, failures=fms[i],
                          control=cms[i]), f"trace {i}: ")


def test_fleet_batched_tables_bit_identical():
    """Per-scenario tables with shared shapes (same scheme over different
    schedules) batch too — the tables leaves ride the scenario axis."""
    cfg = FabricConfig(slice_bytes=4_000)
    wl = _wl(3)
    base = round_robin(N, 1)
    perm = np.roll(np.arange(N), 3)
    relabeled = dataclasses.replace(base, conn=np.where(
        base.conn >= 0, perm[base.conn], base.conn)[:, np.argsort(perm), :])
    tables = [FabricTables.build(s, ucmp(s)) for s in (base, relabeled)]
    gots = simulate_fleet(tables, [wl, wl], cfg, SLICES)
    for i, got in enumerate(gots):
        _assert_results_equal(got, simulate(tables[i], wl, cfg, SLICES),
                              f"tables {i}: ")


def test_fleet_rejects_mixed_mask_presence():
    """Failure/control presence selects the traced program (a static
    branch), so it must agree across the batch — loudly."""
    sched = round_robin(N, 1)
    tables = FabricTables.build(sched, ucmp(sched))
    fm = compile_masks(random_trace(0, sched, SLICES), sched, SLICES)
    with pytest.raises((ValueError, TypeError)):
        simulate_fleet(tables, [_wl(0)] * 2, FabricConfig(slice_bytes=4_000),
                       SLICES, failures=[fm, None])


def test_fleet_telemetry_parity():
    """Telemetry counters ride the scenario axis unchanged: each fleet
    member's per-slice counter rows equal its solo run bit for bit, and
    conservation holds per scenario (ISSUE 8)."""
    sched = round_robin(N, 1)
    tables = FabricTables.build(sched, ucmp(sched))
    cfg = FabricConfig(slice_bytes=4_000, cc_detect=True, pushback=True)
    tele = TelemetryConfig()
    wls = [_wl(s) for s in range(4)]
    fms = [compile_masks(random_trace(s, sched, SLICES, n_events=3), sched,
                         SLICES) for s in range(4)]
    gots = simulate_fleet(tables, wls, cfg, SLICES, failures=fms,
                          telemetry=tele)
    for i, (wl, got) in enumerate(zip(wls, gots)):
        ref = simulate(tables, wl, cfg, SLICES, failures=fms[i],
                       telemetry=tele)
        _assert_results_equal(got, ref, f"seed {i}: ")
        assert toolkit.check_telemetry(got, wl, SLICES) == []


def test_reconfigure_fleet_seed_sweep_bit_identical():
    """reconfigure vmapped over traffic seeds: every ReconfigResult field —
    including the per-epoch history arrays — matches the Python loop."""
    sched = round_robin(N, 1)
    cfg = FabricConfig(slice_bytes=4_000, cc_detect=True)
    rcfg = ReconfigConfig(epoch_slices=16, num_epochs=3, k_hot=2,
                          scheme="hoho")
    wls = [_wl(s) for s in range(4)]
    gots = reconfigure_fleet(sched, wls, cfg, rcfg)
    for i, (wl, got) in enumerate(zip(wls, gots)):
        _assert_results_equal(got, reconfigure(sched, wl, cfg, rcfg),
                              f"seed {i}: ")


def test_reconfigure_fleet_failover_sweep_bit_identical():
    """The full control-plane stack under vmap: healing + 2PC versioned
    installs with timeout, swept over seeded failure + control traces."""
    sched = round_robin(N, 1)
    cfg = FabricConfig(slice_bytes=4_000, cc_detect=True)
    rcfg = ReconfigConfig(epoch_slices=16, num_epochs=3, k_hot=2,
                          scheme="hoho", heal=True, install="2pc",
                          install_timeout=8)
    S = rcfg.epoch_slices * rcfg.num_epochs
    wl = _wl(0)
    fms = [compile_masks(random_trace(s, sched, S, n_events=3), sched, S)
           for s in range(3)]
    cms = [compile_control(random_control_trace(s, N, S, n_events=3), S, N)
           for s in range(3)]
    gots = reconfigure_fleet(sched, [wl] * 3, cfg, rcfg, failures=fms,
                             control=cms)
    for i, got in enumerate(gots):
        _assert_results_equal(
            got, reconfigure(sched, wl, cfg, rcfg, failures=fms[i],
                             control=cms[i]), f"trace {i}: ")
