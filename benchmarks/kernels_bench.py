"""Kernel + dataplane micro-benchmarks.

Interpret-mode Pallas timings measure Python dispatch, not TPU performance —
TPU projections come from the roofline analysis. What IS meaningful on CPU:
the jnp-oracle dataplane throughput (the fabric simulator's hot ops) and the
simulator's packets x slices rate.
"""
from __future__ import annotations

import time

import numpy as np
import jax
import jax.numpy as jnp

from repro.kernels import ops
from repro.core import (FabricConfig, FabricTables, ReconfigConfig, direct,
                        reconfigure, round_robin, synthesize, ucmp)
from repro.core import routing_jnp, topology_jnp
from repro.core.fabric import _group_admit, simulate
from .common import timed


def _bench(fn, *args, iters=5, **kw):
    out = fn(*args, **kw)
    jax.block_until_ready(out)
    t0 = time.time()
    for _ in range(iters):
        out = fn(*args, **kw)
    jax.block_until_ready(out)
    return (time.time() - t0) / iters * 1e6


def _best_of(fn, reps=3):
    """Best-of-``reps`` wall time (seconds) for an already-warm nullary
    call: the whole-simulate rows are single long calls whose run-to-run
    scheduler noise would otherwise dwarf the CI gate tolerance."""
    best = float("inf")
    for _ in range(reps):
        t0 = time.time()
        fn()
        best = min(best, time.time() - t0)
    return best


def run(quick: bool = False):
    rng = np.random.default_rng(0)
    rows = []

    # time-flow lookup oracle (fabric's per-slice hot op) at 108-ToR scale
    n, k, P = 108, 4, 1 << 15
    tbl_n = jnp.asarray(rng.integers(-1, n, (n, n, k)), jnp.int32)
    tbl_d = jnp.asarray(rng.integers(0, 8, (n, n, k)), jnp.int32)
    node = jnp.asarray(rng.integers(0, n, P), jnp.int32)
    dst = jnp.asarray(rng.integers(0, n, P), jnp.int32)
    h = jnp.asarray(rng.integers(0, 2**31, P), jnp.uint32)
    f = jax.jit(lambda *a: ops.time_flow_lookup(*a, impl="ref"))
    us = _bench(f, tbl_n, tbl_d, node, dst, h)
    rows.append(("kern_tfl_ref_32kpkt", us, f"{P/us:.0f}pkt/us"))

    # queue admission at the ISSUE-1 acceptance shape (P = 2^15, the full
    # 108-ToR key space): the XLA stable-sort + segmented-prefix path the
    # fabric runs per slice, vs the sort-free Pallas admission kernel.
    # The interpret-mode kernel row measures Python dispatch only (like the
    # attention row); the meaningful CPU number is admit_xla_p15, the cost
    # the kernel removes on TPU.
    NKEY = 108 * 109
    akey = jnp.asarray(rng.integers(0, NKEY, P), jnp.int32)
    asz = jnp.asarray(rng.integers(64, 1500, P), jnp.int32)
    awant = jnp.asarray(rng.random(P) < 0.7)
    acap = jnp.asarray(rng.integers(0, 150_000, NKEY), jnp.int32)
    f_adm_x = jax.jit(lambda k, s, w, c: _group_admit(k, s, w, c, NKEY))
    us = _bench(f_adm_x, akey, asz, awant, acap)
    rows.append(("admit_xla_p15", us, f"{P/us:.0f}pkt/us"))
    if not quick:
        f_adm_p = jax.jit(lambda k, s, w, c: ops.admission_admit(
            k, s, w, c, num_keys=NKEY, interpret=True))
        us = _bench(f_adm_p, akey, asz, awant, acap, iters=2)
        rows.append(("admit_pallas_interpret_p15", us,
                     "interpret-mode (dispatch cost only)"))

    # flash attention oracle vs naive jnp (CPU walltime, small shape)
    B, Hq, Hkv, L, hd = 1, 4, 2, 512, 64
    q = jnp.asarray(rng.normal(size=(B*Hq, L, hd)), jnp.float32)
    kk = jnp.asarray(rng.normal(size=(B*Hkv, L, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B*Hkv, L, hd)), jnp.float32)
    fr = jax.jit(lambda *a: ops.flash_attention(*a, n_q_heads=Hq,
                                                n_kv_heads=Hkv, impl="ref"))
    rows.append(("kern_attn_ref_512", _bench(fr, q, kk, v), "oracle"))
    if not quick:
        us_p = _bench(lambda *a: ops.flash_attention(
            *a, n_q_heads=Hq, n_kv_heads=Hkv), q, kk, v, iters=2)
        rows.append(("kern_attn_pallas_interp_512", us_p,
                     "interpret-mode (dispatch cost only)"))

    # routing-compiler throughput at paper scale (108 ToRs, T = 107):
    # the time-expanded DP + equal-cost slot collection is the control-plane
    # hot path the fabric depends on before a single packet moves.
    n_route = 32 if quick else 108
    sched_r = round_robin(n_route, 1)
    t0 = time.time()
    r = ucmp(sched_r)
    dt = time.time() - t0
    ent = r.tf_next.size
    rows.append((f"route_ucmp_compile_{n_route}", dt * 1e6,
                 f"{ent/dt/1e6:.1f}Mentry/s"))
    t0 = time.time()
    rd = direct(sched_r)
    dt = time.time() - t0
    rows.append((f"route_direct_compile_{n_route}", dt * 1e6,
                 f"{rd.tf_next.size/dt/1e6:.1f}Mentry/s"))

    # route_recompile: host vs. on-device table compilation, plus the jitted
    # traffic-aware reconfiguration loop that recompiles inside lax.scan
    # (repro.core.reconfigure) — the TA scenario class of the paper's case
    # studies. Host row repeats the ucmp timing above under the comparable
    # name; the device row is the warm jitted repro.core.routing_jnp path.
    t0 = time.time()
    ucmp(sched_r)
    dt_host = time.time() - t0
    rows.append((f"route_recompile_host_{n_route}", dt_host * 1e6,
                 f"{ent/dt_host/1e6:.1f}Mentry/s"))
    conn = jnp.asarray(sched_r.conn)
    f_dev = jax.jit(lambda c: routing_jnp.compile_tables(c, "ucmp"))
    jax.block_until_ready(f_dev(conn))  # warm compile
    iters = 2 if quick else 3
    t0 = time.time()
    for _ in range(iters):
        out = f_dev(conn)
    jax.block_until_ready(out)
    dt_dev = (time.time() - t0) / iters
    rows.append((f"route_recompile_jnp_{n_route}", dt_dev * 1e6,
                 f"{ent/dt_dev/1e6:.1f}Mentry/s ({dt_host/dt_dev:.1f}x host)"))

    wl_r = synthesize("rpc", n_route, 32, slice_bytes=75_000, load=0.3,
                      max_packets=4096, seed=1)
    rcfg = ReconfigConfig(epoch_slices=16, num_epochs=2, scheme="hoho",
                          k_hot=4)
    cfg_r = FabricConfig()
    reconfigure(sched_r, wl_r, cfg_r, rcfg)  # warm compile
    t0 = time.time()
    reconfigure(sched_r, wl_r, cfg_r, rcfg)
    dt = time.time() - t0
    S_r = rcfg.num_epochs * rcfg.epoch_slices
    rows.append((f"route_recompile_loop_{n_route}", dt / S_r * 1e6,
                 f"{S_r/dt:.1f}slice/s+{rcfg.num_epochs/dt:.1f}recompile/s"))

    # on-device TA schedulers at paper scale: the greedy max-weight matching
    # (edmonds analogue) and the BvN decomposition (Sinkhorn + greedy
    # peeling) that reconfigure() can run inside its jitted epoch scan
    tm = jnp.asarray(rng.random((n_route, n_route)) * 100, jnp.float32)
    f_ed = jax.jit(topology_jnp.edmonds_conn)
    us = _bench(f_ed, tm, iters=3)
    rows.append((f"ta_match_edmonds_{n_route}", us, f"{n_route}-node matching"))
    f_bvn = jax.jit(lambda m: topology_jnp.bvn_conn(m, num_slices=8,
                                                    max_perms=8))
    us = _bench(f_bvn, tm, iters=3)
    rows.append((f"ta_match_bvn_{n_route}", us, "8-perm decomposition"))

    # the full demand-aware loop: measure -> BvN -> recompile -> simulate,
    # one XLA program per run (the Mordia scenario of the paper's §4.2)
    rcfg_b = ReconfigConfig(epoch_slices=16, num_epochs=2, scheme="direct",
                            scheduler="bvn", bvn_slices=8, bvn_perms=8)
    reconfigure(sched_r, wl_r, cfg_r, rcfg_b)  # warm compile
    t0 = time.time()
    reconfigure(sched_r, wl_r, cfg_r, rcfg_b)
    dt = time.time() - t0
    S_b = rcfg_b.num_epochs * rcfg_b.epoch_slices
    rows.append((f"reconfig_bvn_loop_{n_route}", dt / S_b * 1e6,
                 f"{S_b/dt:.1f}slice/s+{rcfg_b.num_epochs/dt:.1f}bvn-recompile/s"))

    # fabric simulator throughput
    n2 = 16
    sched = round_robin(n2, 1)
    wl = synthesize("rpc", n2, 60, slice_bytes=10_000, load=0.3,
                    max_packets=4000, seed=1)
    tables = FabricTables.build(sched, ucmp(sched))
    cfg = FabricConfig(slice_bytes=10_000)
    S = 150
    simulate(tables, wl, cfg, S)  # warm compile
    dt = _best_of(lambda: simulate(tables, wl, cfg, S))
    rate = wl.num_packets * S / dt
    rows.append(("fabric_sim_rate", dt * 1e6, f"{rate/1e6:.2f}Mpkt-slice/s"))

    # push-back simulate under receiver-buffer pressure: the rx cut rejects
    # every slice, so the push-back-aware backlog filter (ISSUE 5) decides
    # how much of the packet vector later hops re-sort — these rows track
    # that win (the filter was previously disabled under push-back)
    wl_pb = synthesize("rpc", n2, 60, slice_bytes=10_000, load=4.0,
                       max_packets=4000, seed=1)
    cfg_pb = FabricConfig(slice_bytes=10_000, pushback=True,
                          switch_buffer=16_000)
    S_pb = 60
    simulate(tables, wl_pb, cfg_pb, S_pb)  # warm compile
    dt = _best_of(lambda: simulate(tables, wl_pb, cfg_pb, S_pb))
    rows.append(("fabric_sim_pushback", dt * 1e6,
                 f"{wl_pb.num_packets*S_pb/dt/1e6:.2f}Mpkt-slice/s"))

    # fabric simulator at P = 2^15 (the ISSUE-1 acceptance shape), plain
    # and under push-back (where the rx backlog filter carries the load)
    if not quick:
        wl2 = synthesize("rpc", n2, 60, slice_bytes=10_000, load=4.0,
                         max_packets=1 << 15, seed=1)
        simulate(tables, wl2, cfg, S)  # warm compile
        dt = _best_of(lambda: simulate(tables, wl2, cfg, S))
        rate = wl2.num_packets * S / dt
        rows.append(("fabric_sim_rate_32k", dt * 1e6,
                     f"{rate/1e6:.2f}Mpkt-slice/s"))
        simulate(tables, wl2, cfg_pb, S_pb)  # warm compile
        dt = _best_of(lambda: simulate(tables, wl2, cfg_pb, S_pb))
        rows.append(("fabric_sim_pushback_32k", dt * 1e6,
                     f"{wl2.num_packets*S_pb/dt/1e6:.2f}Mpkt-slice/s"))
    return rows
