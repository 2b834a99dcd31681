"""The fabric's Pallas kernels compile for a TPU v5e — checked without a chip.

The TPU compiler ships with jaxlib's libtpu and compiles for a described,
unattached ``v5e:2x2`` topology. Interpret-mode tests cannot see what the
Mosaic compiler refuses (gathers it cannot lower, unaligned blocks, VMEM
overflow); these compiles can. Each asserts that the kernel reached the
program as a Mosaic custom call, i.e. that it was compiled, not interpreted.

Only one process may load libtpu at a time, so the topology is described
inside a module fixture (never at import), and every test of this kind lives
in this one file.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import (FabricConfig, FabricTables, round_robin, synthesize,
                        ucmp)
from repro.core import fabric, tracing
from repro.kernels.admission import admission_admit
from repro.kernels.time_flow_lookup import time_flow_lookup

import hlo_text

PAPER_TORS = 108
P = 1 << 15


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")   # no compiler logs in /tmp
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no libtpu, or it is held elsewhere
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile().as_text()


@pytest.mark.parametrize("K", [1, 4])
def test_time_flow_lookup_compiles_for_v5e(one_chip, K):
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    tbl = S((PAPER_TORS, PAPER_TORS, K), jnp.int32)
    vec = S((P,), jnp.int32)
    txt = _compiled_text(time_flow_lookup, tbl, tbl, vec, vec,
                         S((P,), jnp.uint32))
    assert "tpu_custom_call" in txt


def test_admission_compiles_for_v5e(one_chip):
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    nk = PAPER_TORS * (PAPER_TORS + 1)
    vec = S((P,), jnp.int32)
    txt = _compiled_text(
        lambda k, s, w, c: admission_admit(k, s, w, c, num_keys=nk),
        vec, vec, S((P,), jnp.bool_), S((nk,), jnp.int32))
    assert "tpu_custom_call" in txt


def _compiled_scan_text(one_chip, cfg):
    """The fabric scan at 16 ToRs and 4,096 packets, compiled for a v5e."""
    n = 16
    sched = round_robin(n, 1, slice_us=10.0)
    tables = FabricTables.build(sched, ucmp(sched))
    wl = synthesize("kvstore", n, 16, slice_bytes=125_000, load=0.4,
                    max_packets=4096, seed=0)
    j = dict(conn=tables.conn, tf_next=tables.tf_next, tf_dep=tables.tf_dep,
             inj_next=tables.inj_next, inj_dep=tables.inj_dep,
             first_direct=tables.first_direct, src=wl.src, dst=wl.dst,
             size=wl.size, t_inject=wl.t_inject, flow=wl.flow, seq=wl.seq,
             is_eleph=wl.is_eleph)
    shapes = {k: jax.ShapeDtypeStruct(
        np.shape(v), jnp.bool_ if k == "is_eleph" else jnp.int32,
        sharding=one_chip) for k, v in j.items()}
    return fabric._simulate_jit.lower(shapes, cfg, 48, True, wl.num_flows,
                                      None).compile().as_text()


def test_simulate_with_pallas_backends_compiles_for_v5e(one_chip):
    """Both kernels lower inside the fabric's per-slice scan."""
    cfg = FabricConfig(slice_bytes=125_000, lookup_impl="pallas",
                       admit_impl="pallas")
    assert "tpu_custom_call" in _compiled_scan_text(one_chip, cfg)


@pytest.mark.parametrize("pushback", [False, True],
                         ids=["pushback_off", "pushback_on"])
def test_backlog_gather_stays_in_a_conditional_for_v5e(one_chip, pushback):
    """The TPU compiler keeps the backlog filter's gather in a conditional
    branch of its own at each hop after the first, and turns none of them
    into a select that would run it every hop."""
    cfg = FabricConfig(slice_bytes=125_000, cc_detect=True, pushback=pushback)
    words = {w for s in tracing.SCOPES for w in s.split("/")} | {"gather"}
    found = hlo_text.scoped_gathers(_compiled_scan_text(one_chip, cfg), words)
    gated = [b for path, b in found if path == tracing.BACKLOG_GATHER]
    assert gated and all(gated)
    assert len(set(gated)) == cfg.hops_per_slice - 1
    assert not [p for p, _ in found if p == "fabric/hop/backlog_filter"]
