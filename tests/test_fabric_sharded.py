"""Multi-device differential harness for the sharded fabric (ISSUE 7): under
the forced 8-device CPU mesh (conftest ``XLA_FLAGS``), ``simulate_sharded``
must be **bit-identical** to the single-device golden ``simulate`` across all
8 routing schemes × push-back × failure masks × control faults, at shard
counts that do not divide the ToR or packet counts, and under both admission
backends. Plus: the ``toolkit.check_sharding`` soundness checker on every
differential run, the ``cap_offset`` admission dispatch hook, and the
per-device dense-mask footprint regression at paper scale (108 ToRs).
"""
import dataclasses

import numpy as np
import pytest

from repro.core import (FabricConfig, FabricTables, direct, vlb, opera, ucmp,
                        hoho, ecmp, wcmp, ksp, round_robin, simulate,
                        simulate_sharded, synthesize, compile_masks,
                        random_trace, compile_control, random_control_trace,
                        toolkit)
from repro.distributed import sharding as dshard
from repro.kernels import ops

pytestmark = pytest.mark.multidevice

N = 8
SLICES = 48
SCHEMES = [direct, vlb, opera, ucmp, hoho, ecmp, wcmp, ksp]


def _workload(**kw):
    base = dict(slice_bytes=4_000, load=0.9, max_packets=420, seed=11)
    base.update(kw)
    return synthesize("rpc", N, 24, **base)


def _tables(alg):
    sched = round_robin(N, 1)
    return FabricTables.build(sched, alg(sched))


def _masks(sched, seed=3):
    fails = compile_masks(random_trace(seed, sched, SLICES), sched, SLICES)
    ctrl = compile_control(random_control_trace(seed + 1, N, SLICES),
                           SLICES, N)
    return fails, ctrl


def _assert_results_equal(a, b):
    for f in dataclasses.fields(a):
        np.testing.assert_array_equal(
            getattr(a, f.name), getattr(b, f.name), err_msg=f.name)


def _diff(tables, wl, cfg, num_shards, failures=None, control=None):
    """The differential assertion: sharded == single-device, bit for bit,
    and the sharding soundness checker holds."""
    ref = simulate(tables, wl, cfg, SLICES, failures=failures,
                   control=control)
    got, dbg = simulate_sharded(tables, wl, cfg, SLICES,
                                num_shards=num_shards, failures=failures,
                                control=control, with_debug=True)
    _assert_results_equal(got, ref)
    assert toolkit.check_sharding(got, dbg, wl, SLICES) == []
    return ref


@pytest.mark.parametrize("alg", SCHEMES, ids=lambda a: a.__name__)
def test_all_schemes_bit_identical_8dev(alg, eight_devices):
    """All 8 schemes, full mechanism pressure: push-back + failure masks +
    control faults on the full 8-device mesh."""
    sched = round_robin(N, 1)
    tables = FabricTables.build(sched, alg(sched))
    cfg = FabricConfig(slice_bytes=4_000, cc_detect=True, pushback=True)
    fails, ctrl = _masks(sched)
    _diff(tables, _workload(), cfg, 8, failures=fails, control=ctrl)


@pytest.mark.parametrize("alg", SCHEMES, ids=lambda a: a.__name__)
def test_all_schemes_bit_identical_plain(alg, eight_devices):
    """All 8 schemes without masks (the default-config golden path)."""
    _diff(_tables(alg), _workload(), FabricConfig(slice_bytes=4_000), 8)


@pytest.mark.parametrize("num_shards", [2, 3, 5, 8])
def test_shard_counts_not_dividing(num_shards, eight_devices):
    """Shard counts that do not divide N=8 ToRs (3, 5) or the 420-packet
    population (8): block padding must stay semantically invisible."""
    sched = round_robin(N, 1)
    tables = FabricTables.build(sched, vlb(sched))
    cfg = FabricConfig(slice_bytes=4_000, cc_detect=True, pushback=True)
    fails, ctrl = _masks(sched, seed=7)
    _diff(tables, _workload(), cfg, num_shards, failures=fails, control=ctrl)


@pytest.mark.parametrize("over", [
    dict(offload=True, offload_horizon=1, switch_buffer=30_000),
    dict(flow_pausing=True),
    dict(elec_bytes=2_000, cc_detect=True, pushback=True,
         switch_buffer=9_000),
    dict(hops_per_slice=1),
], ids=["offload", "flow-pausing", "elec-pushback", "single-hop"])
def test_mechanism_matrix_bit_identical(over, eight_devices):
    """§5.2 mechanism extras under sharding (offloading, flow pausing,
    hybrid electrical egress + push-back under buffer pressure)."""
    _diff(_tables(vlb), _workload(), FabricConfig(slice_bytes=4_000, **over),
          4)


@pytest.mark.parametrize("pushback", [False, True],
                         ids=["pushback_off", "pushback_on"])
@pytest.mark.parametrize("slice_bytes,shut", [(20_000, True), (4_000, False)],
                         ids=["gate-shut", "gate-open"])
def test_backlog_gate_bit_identical(slice_bytes, shut, pushback,
                                    eight_devices):
    """UCMP multi-hop under the backlog filter's gate, shut all run (no
    rejection on 20 KB circuits) and open at hops >= 1 (4 KB circuits):
    the cuts are exchanged before the gate reads them, so every shard
    takes the same branch."""
    cfg = FabricConfig(slice_bytes=slice_bytes, cc_detect=True,
                       pushback=pushback)
    ref = _diff(_tables(ucmp), _workload(seed=0), cfg, 4)
    # a rejection misses its slice, and only a rejection opens the gate
    assert (ref.slice_miss.sum() == 0) == shut


@pytest.mark.parametrize("impls", [
    dict(admit_impl="pallas-interpret"),
    dict(lookup_impl="pallas-interpret"),
], ids=["pallas-admit", "pallas-lookup"])
def test_pallas_backends_under_shard_map(impls, eight_devices):
    """The Pallas kernels dispatch unchanged under shard_map: the cap-shift
    admission formulation feeds them shifted capacities, so the backends
    stay swappable on the sharded path too."""
    cfg = FabricConfig(slice_bytes=4_000, cc_detect=True, **impls)
    _diff(_tables(hoho), _workload(), cfg, 4)


def test_telemetry_parity_sharded(eight_devices):
    """Telemetry counter rows are psum-reconciled inside the sharded step:
    with telemetry on, every counter frame equals the single-device run bit
    for bit, the non-telemetry fields stay untouched, and conservation
    holds on the sharded result (ISSUE 8)."""
    from repro.core import TelemetryConfig
    sched = round_robin(N, 1)
    tables = FabricTables.build(sched, ucmp(sched))
    cfg = FabricConfig(slice_bytes=4_000, cc_detect=True, pushback=True)
    fails, ctrl = _masks(sched)
    tele = TelemetryConfig()
    wl = _workload()
    ref = simulate(tables, wl, cfg, SLICES, failures=fails, control=ctrl,
                   telemetry=tele)
    got = simulate_sharded(tables, wl, cfg, SLICES, num_shards=8,
                           failures=fails, control=ctrl, telemetry=tele)
    for f in dataclasses.fields(ref):
        if f.name == "telemetry":
            continue
        np.testing.assert_array_equal(getattr(got, f.name),
                                      getattr(ref, f.name), err_msg=f.name)
    for f in dataclasses.fields(ref.telemetry):
        if f.name == "lat_edges":
            assert got.telemetry.lat_edges == ref.telemetry.lat_edges
            continue
        np.testing.assert_array_equal(
            getattr(got.telemetry, f.name), getattr(ref.telemetry, f.name),
            err_msg=f"telemetry.{f.name}")
    assert toolkit.check_telemetry(got, wl, SLICES) == []


def test_ownership_debug_fields(eight_devices):
    """with_debug exposes the partition: owners are the contiguous-block
    map, and every admitting shard is the owner (the checker's core
    invariant, asserted here directly on the raw debug dict)."""
    wl = _workload()
    res, dbg = simulate_sharded(_tables(ucmp), wl,
                                FabricConfig(slice_bytes=4_000), SLICES,
                                num_shards=8, with_debug=True)
    P = wl.num_packets
    assert dbg["num_shards"] == 8
    assert dbg["packet_block"] == dshard.block_len(P, 8)
    np.testing.assert_array_equal(
        dbg["owner"], np.arange(P) // dshard.block_len(P, 8))
    adm = dbg["adm_shard"]
    assert adm.shape == (P,)
    hopped = np.asarray(res.nhops) > 0
    np.testing.assert_array_equal(adm[hopped], dbg["owner"][hopped])
    assert np.all(adm[~hopped] == -1)


def test_admission_cap_offset_dispatch():
    """ops.admission_admit(cap_offset=...) is the shard_map dispatch hook:
    shifting capacities by a prior-shard byte prefix equals admitting
    against the reduced budget — for both backends, bit for bit."""
    rng = np.random.default_rng(5)
    P, K = 257, 6
    key = rng.integers(0, K, P).astype(np.int32)
    size = rng.integers(1, 1500, P).astype(np.int32)
    want = rng.random(P) < 0.8
    cap = rng.integers(0, 40_000, K).astype(np.int32)
    offs = rng.integers(0, 20_000, K).astype(np.int32)
    for impl in ("ref", "pallas"):
        kw = dict(num_keys=K, impl=impl)
        if impl == "pallas":
            kw["interpret"] = True
        a_adm, a_used = ops.admission_admit(key, size, want, cap, cap_offset=offs,
                                            **kw)
        b_adm, b_used = ops.admission_admit(key, size, want, cap - offs, **kw)
        np.testing.assert_array_equal(np.asarray(a_adm), np.asarray(b_adm))
        np.testing.assert_array_equal(np.asarray(a_used), np.asarray(b_used))


def test_versioned_tables_rejected_when_sharded(eight_devices):
    """has_vers (mid-install versioned tables) is a reconfigure-only
    feature; the sharded fabric must refuse it loudly, not silently
    diverge."""
    import repro.core.fabric as fabric
    j = {"tf_next_v": None}
    with pytest.raises(AssertionError):
        fabric._make_step(j, FabricConfig(), True, 1, axis="tor",
                          num_shards=2)


# ---------------------------------------------------------------------------
# Dense-mask footprint regression (ISSUE 7 satellite): each device holds only
# its owned ToR rows of link_cap[S, N, N] / the control tensors.
# ---------------------------------------------------------------------------

PAPER_N = 108          # the paper's testbed ToR count
PAPER_S = 1000


@pytest.mark.parametrize("num_shards,rows", [(4, 27), (8, 14)])
def test_mask_rows_sharded_footprint_paper_scale(num_shards, rows):
    """At 108 ToRs × 10^3 slices the replicated f32 link_cap is ~46.7 MB
    per device; row-sharding pins it to S * ceil(N/D) * N * 4 bytes."""
    assert dshard.block_len(PAPER_N, num_shards) == rows
    per_dev = dshard.node_rows_bytes_per_device(PAPER_S, PAPER_N, num_shards)
    assert per_dev == PAPER_S * rows * PAPER_N * 4
    full = PAPER_S * PAPER_N * PAPER_N * 4
    assert per_dev * num_shards < full + PAPER_S * rows * PAPER_N * 4
    # the headline numbers, pinned: 11.664 MB at D=4, 6.048 MB at D=8
    assert per_dev == {4: 11_664_000, 8: 6_048_000}[num_shards]


def test_mask_rows_padded_shapes_paper_scale():
    """pad_node_rows at paper scale: D=8 pads 108 rows to 112 (4 phantom
    always-healthy ToRs), and each shard's slice is exactly [S, 14, N]."""
    lc = np.ones((4, PAPER_N, PAPER_N), np.float32)   # S=4 stand-in
    padded = dshard.pad_node_rows(lc, 8, 1.0)
    assert padded.shape == (4, 112, PAPER_N)
    assert np.all(padded[:, PAPER_N:] == 1.0)
    assert padded.shape[1] // 8 == 14
