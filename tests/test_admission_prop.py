"""Property-based admission impl-boundary sweep (hypothesis): random
schedules x {admit_impl} x {push-back on/off} x {failures on/off} — the
Pallas admission kernel (interpret mode) must be bit-identical to the XLA
sort path on every draw, and the push-back-aware backlog filter must keep
push-back runs bit-identical regardless of backend.

The deterministic subset (plus the seed-reference pins) lives in
``test_admission.py``; in CI this module always runs
(``tests/conftest.py`` hard-errors there when hypothesis is missing).
"""
import dataclasses

import numpy as np
from hypothesis import given, settings, strategies as st

from repro.core import (FabricConfig, FabricTables, compile_masks,
                        random_trace, simulate, synthesize, ucmp)
from repro.core.fabric import _group_admit
from repro.kernels import ops

from invariant_cases import random_schedule

N = 6
SLICES = 16


def _assert_results_equal(a, b):
    for f in dataclasses.fields(a):
        np.testing.assert_array_equal(
            getattr(a, f.name), getattr(b, f.name), err_msg=f.name)


@settings(max_examples=25, deadline=None)
@given(seed=st.integers(0, 2**16), P=st.integers(1, 600),
       nk=st.integers(1, 400), maxcap=st.integers(0, 8000),
       p_want=st.floats(0.0, 1.0))
def test_admission_op_parity_random(seed, P, nk, maxcap, p_want):
    """Raw-op property: kernel == oracle == XLA sort path on arbitrary
    (P, num_keys, capacity, want-density) draws."""
    import jax.numpy as jnp
    rng = np.random.default_rng(seed)
    key = jnp.asarray(rng.integers(0, nk, P), jnp.int32)
    size = jnp.asarray(rng.integers(0, 2000, P), jnp.int32)
    want = jnp.asarray(rng.random(P) < p_want)
    cap = jnp.asarray(rng.integers(0, maxcap + 1, nk), jnp.int32)
    a_k, u_k = ops.admission_admit(key, size, want, cap, num_keys=nk,
                                   interpret=True)
    a_x, u_x = _group_admit(key, size, want, cap, nk)
    np.testing.assert_array_equal(np.asarray(a_k), np.asarray(a_x))
    np.testing.assert_array_equal(np.asarray(u_k), np.asarray(u_x))


@settings(max_examples=8, deadline=None)
@given(seed=st.integers(0, 2**16), T=st.integers(1, 3),
       pushback=st.booleans(), failures=st.booleans(),
       load=st.floats(0.5, 3.0))
def test_fabric_admit_impl_parity_random(seed, T, pushback, failures, load):
    """Fabric property: on a random schedule and workload, the jitted run
    is bit-identical across admission backends, under push-back (tiny
    receiver buffers, so the rx cut fires) and under failure masks."""
    sched = random_schedule(seed, N, T, U=2)
    tables = FabricTables.build(sched, ucmp(sched))
    wl = synthesize("rpc", N, 12, slice_bytes=4_000, load=load,
                    max_packets=150, seed=seed % 97)
    masks = None
    if failures:
        masks = compile_masks(random_trace(seed ^ 0xFA11, sched, SLICES),
                              sched, SLICES)
    cfg = FabricConfig(slice_bytes=4_000, pushback=pushback,
                       switch_buffer=12_000)
    pal = dataclasses.replace(cfg, admit_impl="pallas-interpret")
    _assert_results_equal(simulate(tables, wl, cfg, SLICES, masks),
                          simulate(tables, wl, pal, SLICES, masks))
