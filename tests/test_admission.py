"""Deterministic parity suite for the queue-admission impl boundary
(ISSUE 5): the sort-free Pallas admission kernel
(``repro.kernels.admission``, interpret mode on CPU) must be bit-identical
to the XLA stable-sort path at every level — the raw op, the jitted fabric
across all eight routing schemes, push-back and failure-masked
configurations, and the reconfiguration epoch scan. The push-back-aware
backlog filter is additionally pinned against the seed reference formulation
(``tests/fabric_ref.py``) under receiver-buffer pressure.

The hypothesis widening of these cases lives in ``test_admission_prop.py``.
"""
import dataclasses

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import (FabricConfig, FabricTables, FailureTrace,
                        compile_masks, direct, ecmp, hoho, ksp, opera,
                        reconfigure, ReconfigConfig, round_robin, simulate,
                        synthesize, ucmp, vlb, wcmp)
from repro.core.fabric import _group_admit
from repro.kernels import ops

from fabric_ref import simulate_ref

N = 8
SLICES = 24
ALL_SCHEMES = [("direct", direct), ("vlb", vlb), ("opera", opera),
               ("ucmp", ucmp), ("hoho", hoho), ("ecmp", ecmp),
               ("wcmp", wcmp), ("ksp", ksp)]


def _assert_results_equal(a, b):
    for f in dataclasses.fields(a):
        np.testing.assert_array_equal(
            getattr(a, f.name), getattr(b, f.name), err_msg=f.name)


def _workload(max_packets=300, load=0.9, seed=11):
    return synthesize("rpc", N, 18, slice_bytes=4_000, load=load,
                      max_packets=max_packets, seed=seed)


# ---------------------------------------------------------------------------
# raw op: kernel vs jnp oracle vs the fabric's XLA formulation
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("P", [1, 7, 255, 1000, 4097])
@pytest.mark.parametrize("nk", [5, 129, 300])
def test_admission_kernel_matches_oracle_and_xla(P, nk):
    """Padding of both the packet axis (to the tile size) and the key axis
    (to a lane multiple) must not change a single admission bit."""
    rng = np.random.default_rng(P * 1000 + nk)
    key = jnp.asarray(rng.integers(0, nk, P), jnp.int32)
    size = jnp.asarray(rng.integers(0, 2000, P), jnp.int32)
    want = jnp.asarray(rng.random(P) < 0.7)
    cap = jnp.asarray(rng.integers(0, 6000, nk), jnp.int32)
    a_k, u_k = ops.admission_admit(key, size, want, cap, num_keys=nk,
                                   interpret=True)
    a_r, u_r = ops.admission_admit(key, size, want, cap, num_keys=nk,
                                   impl="ref")
    a_x, u_x = _group_admit(key, size, want, cap, nk)
    assert a_k.shape == (P,) and u_k.shape == (nk,)
    np.testing.assert_array_equal(np.asarray(a_k), np.asarray(a_r))
    np.testing.assert_array_equal(np.asarray(a_k), np.asarray(a_x))
    np.testing.assert_array_equal(np.asarray(u_k), np.asarray(u_r))
    np.testing.assert_array_equal(np.asarray(u_k), np.asarray(u_x))


def test_admission_kernel_fifo_semantics():
    """Hand-built case: FIFO within a group — the first packets that fit
    win, a rejected packet's bytes still count against its successors."""
    key = jnp.asarray([0, 1, 0, 0, 1], jnp.int32)
    size = jnp.asarray([60, 50, 30, 10, 60], jnp.int32)
    want = jnp.asarray([True, True, True, True, True])
    cap = jnp.asarray([100, 100], jnp.int32)
    adm, used = ops.admission_admit(key, size, want, cap, num_keys=2, bp=2,
                                     interpret=True)
    # group 0: 60 in, 30 in, 10 in (100 exactly); group 1: 50 in, 60 out
    np.testing.assert_array_equal(np.asarray(adm),
                                  [True, True, True, True, False])
    np.testing.assert_array_equal(np.asarray(used), [100, 50])


def test_admission_kernel_interpret_smoke():
    """The CPU CI smoke test the ISSUE asks for: the pallas_call itself
    (interpret mode) runs under jit with multiple tiles and a non-aligned
    key space."""
    import jax
    rng = np.random.default_rng(0)
    P, nk = 1111, 77
    f = jax.jit(lambda k, s, w, c: ops.admission_admit(
        k, s, w, c, num_keys=nk, bp=128, interpret=True))
    adm, used = f(jnp.asarray(rng.integers(0, nk, P), jnp.int32),
                  jnp.asarray(rng.integers(1, 1500, P), jnp.int32),
                  jnp.asarray(rng.random(P) < 0.5),
                  jnp.asarray(rng.integers(0, 20_000, nk), jnp.int32))
    assert adm.dtype == bool and int(adm.sum()) > 0
    assert int(used.sum()) > 0


# ---------------------------------------------------------------------------
# fabric-level: admit_impl="pallas-interpret" vs "xla", all schemes
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name,alg", ALL_SCHEMES, ids=[s for s, _ in ALL_SCHEMES])
def test_fabric_admit_impl_parity_all_schemes(name, alg):
    sched = round_robin(N, 1)
    tables = FabricTables.build(sched, alg(sched))
    wl = _workload()
    base = FabricConfig(slice_bytes=4_000)
    pal = dataclasses.replace(base, admit_impl="pallas-interpret")
    _assert_results_equal(simulate(tables, wl, base, SLICES),
                          simulate(tables, wl, pal, SLICES))


@pytest.mark.parametrize("over", [
    dict(pushback=True, switch_buffer=20_000),
    dict(pushback=True, offload=True, offload_horizon=1,
         switch_buffer=12_000),
], ids=["pushback", "pushback-offload-tinybuf"])
def test_fabric_admit_impl_parity_pushback(over):
    """Push-back routes a second admission (the receiver-buffer cut)
    through the impl boundary; tiny buffers make it actually reject."""
    sched = round_robin(N, 1)
    tables = FabricTables.build(sched, ucmp(sched))
    wl = _workload(load=2.0)
    base = FabricConfig(slice_bytes=4_000, **over)
    pal = dataclasses.replace(base, admit_impl="pallas-interpret")
    a = simulate(tables, wl, base, SLICES)
    assert int(a.slice_miss.sum()) > 0  # rejections really occurred
    _assert_results_equal(a, simulate(tables, wl, pal, SLICES))


def test_fabric_admit_impl_parity_failure_masked():
    """The failure-masked capacity recompute feeds the same admission
    boundary: dead circuits admit nothing under both backends."""
    sched = round_robin(N, 1)
    tables = FabricTables.build(sched, hoho(sched))
    wl = _workload()
    masks = compile_masks(
        FailureTrace().link_flap(0, 1, 4).tor_outage(3, 8, 16)
        .degrade(2, 5, 0.5, 2), sched, SLICES)
    base = FabricConfig(slice_bytes=4_000)
    pal = dataclasses.replace(base, admit_impl="pallas-interpret")
    _assert_results_equal(simulate(tables, wl, base, SLICES, masks),
                          simulate(tables, wl, pal, SLICES, masks))


def test_fabric_admit_impl_rejects_unknown():
    sched = round_robin(N, 1)
    tables = FabricTables.build(sched, ucmp(sched))
    cfg = FabricConfig(admit_impl="sort")
    with pytest.raises(ValueError, match="admit_impl"):
        simulate(tables, _workload(), cfg, 4)


# ---------------------------------------------------------------------------
# push-back-aware backlog filter vs the seed reference under rx pressure
# ---------------------------------------------------------------------------

def test_pushback_filter_bit_identical_under_rx_pressure():
    """Overloaded receivers with tiny buffers: the rx cut rejects, the new
    rx/elec backlog filters engage, and the run must stay bit-identical to
    the unfiltered seed reference."""
    wl = synthesize("rpc", N, 18, slice_bytes=4_000, load=3.0,
                    max_packets=900, seed=7)
    sched = round_robin(N, 1)
    tables = FabricTables.build(sched, ucmp(sched))
    cfg = FabricConfig(slice_bytes=4_000, pushback=True,
                       switch_buffer=10_000)
    res = simulate(tables, wl, cfg, SLICES)
    assert int(res.slice_miss.sum()) > 0
    _assert_results_equal(res, simulate_ref(tables, wl, cfg, SLICES))


def test_pushback_filter_bit_identical_with_electrical():
    """All-electrical Clos tables under overload: every candidate sits in
    an rx-exempt (loc, N) group, so the push-back electrical capacity cut
    does all the filtering — and must stay bit-identical to the seed
    reference."""
    from repro.core import clos_routing
    wl = synthesize("rpc", N, 18, slice_bytes=4_000, load=3.0,
                    max_packets=900, seed=9)
    sched = round_robin(N, 1)
    tables = FabricTables.build(sched, clos_routing(N))
    cfg = FabricConfig(slice_bytes=4_000, elec_bytes=2_000, pushback=True,
                       switch_buffer=10_000)
    res = simulate(tables, wl, cfg, SLICES)
    assert int(res.slice_miss.sum()) > 0
    _assert_results_equal(res, simulate_ref(tables, wl, cfg, SLICES))


# ---------------------------------------------------------------------------
# reconfiguration epoch scan through the kernel
# ---------------------------------------------------------------------------

def test_reconfigure_admit_impl_parity():
    sched = round_robin(N, 1)
    wl = _workload(seed=3)
    rcfg = ReconfigConfig(epoch_slices=8, num_epochs=2, scheme="hoho",
                          k_hot=2)
    base = FabricConfig(slice_bytes=4_000)
    pal = dataclasses.replace(base, admit_impl="pallas-interpret")
    a = reconfigure(sched, wl, base, rcfg)
    b = reconfigure(sched, wl, pal, rcfg)
    for f in dataclasses.fields(a):
        va, vb = getattr(a, f.name), getattr(b, f.name)
        if isinstance(va, np.ndarray):
            np.testing.assert_array_equal(va, vb, err_msg=f.name)
