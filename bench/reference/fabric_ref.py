"""Plain reference of the fabric data plane, copied from the repository's
seed formulation (``tests/fabric_ref.py``) so that the benchmark's yardstick
cannot change with the program: occupancy recomputed from scratch at every
enqueue check, every phase executed on every packet in every slice that
has a packet yet to inject or on a switch, no compaction and no kernels.
(A slice without one changes no state, since every phase's mask is empty;
only its counters are made.) It imports nothing of the program; tables,
workload and configuration are plain numpy arrays and a dataclass.

``simulate_ref(tables, wl, cfg, num_slices)`` returns the fabric's result
fields as a dict of numpy arrays (``t_deliver loc_final nhops
delivered_bytes dropped buf_bytes offl_bytes blocked_inj slice_miss
reorder_cnt``). The data plane is integer, so a sound program equals it
bit for bit.
"""
from __future__ import annotations

import dataclasses
import functools

import numpy as np
import jax
import jax.numpy as jnp

__all__ = ["RefConfig", "simulate_ref", "NOT_INJECTED", "DELIVERED",
           "DROPPED", "RESULT_FIELDS"]

NOT_INJECTED = -1
DELIVERED = -2
DROPPED = -3
RESULT_FIELDS = ("t_deliver", "loc_final", "nhops", "delivered_bytes",
                 "dropped", "buf_bytes", "offl_bytes", "blocked_inj",
                 "slice_miss", "reorder_cnt")


@dataclasses.dataclass(frozen=True)
class RefConfig:
    """The fabric parameters the reference reads (the program's defaults)."""

    slice_bytes: int = 75_000
    elec_bytes: int = 0
    switch_buffer: int = 64 << 20
    hops_per_slice: int = 4
    max_hops: int = 16
    cc_detect: bool = True
    pushback: bool = False
    offload: bool = False
    offload_horizon: int = 2
    flow_pausing: bool = False
    congestion_threshold: int = 1 << 30
    # slices by which transit and re-lookups read an older time-flow table;
    # 0 in the reference, 1 in the control (bench/check.py)
    table_lag: int = 0


def _hash32(x: jnp.ndarray) -> jnp.ndarray:
    x = x.astype(jnp.uint32)
    x = (x ^ (x >> 16)) * jnp.uint32(0x7FEB352D)
    x = (x ^ (x >> 15)) * jnp.uint32(0x846CA68B)
    return x ^ (x >> 16)


def _lookup(next_tbl, dep_tbl, t, node, dst, hashv):
    Tr, _, _, K = next_tbl.shape
    tm = t % Tr
    row_n = next_tbl[tm, node, dst]
    row_d = dep_tbl[tm, node, dst]
    nvalid = jnp.sum(row_n >= 0, axis=-1)
    slot = (hashv % jnp.maximum(nvalid, 1).astype(jnp.uint32)).astype(jnp.int32)
    nxt = jnp.take_along_axis(row_n, slot[:, None], axis=-1)[:, 0]
    off = jnp.take_along_axis(row_d, slot[:, None], axis=-1)[:, 0]
    return nxt, off


def _group_admit(key, size, want, cap_left, num_keys):
    P = key.shape[0]
    key_eff = jnp.where(want, key, num_keys)
    order = jnp.argsort(key_eff, stable=True)
    k_s = key_eff[order]
    sz_s = jnp.where(want, size, 0)[order]
    cs = jnp.cumsum(sz_s)
    cs_excl = cs - sz_s
    is_start = jnp.concatenate([jnp.array([True]), k_s[1:] != k_s[:-1]])
    base = jax.lax.cummax(jnp.where(is_start, cs_excl, -1))
    prefix = cs_excl - base
    cap_s = jnp.concatenate([cap_left, jnp.zeros((1,), cap_left.dtype)])[k_s]
    adm_s = (prefix + sz_s <= cap_s) & (k_s < num_keys)
    admitted = jnp.zeros((P,), bool).at[order].set(adm_s)
    used = jax.ops.segment_sum(jnp.where(admitted, size, 0), key_eff,
                               num_segments=num_keys + 1)[:num_keys]
    return admitted, used


def _build_caps(conn_t, cfg: RefConfig, N: int):
    caps = jnp.zeros((N * (N + 1),), jnp.int32)
    U = conn_t.shape[1]
    rows = jnp.arange(N, dtype=jnp.int32)
    for k in range(U):
        peer = conn_t[:, k]
        keyk = rows * (N + 1) + jnp.where(peer >= 0, peer, N)
        add = jnp.where(peer >= 0, jnp.int32(cfg.slice_bytes), 0)
        caps = caps.at[keyk].add(add)
    caps = caps.at[rows * (N + 1) + N].add(jnp.int32(cfg.elec_bytes))
    return caps


def simulate_ref(tables: dict, wl: dict, cfg: RefConfig,
                 num_slices: int) -> dict:
    """Run the reference for ``num_slices`` slices. ``tables`` holds
    ``conn tf_next tf_dep inj_next inj_dep first_direct`` and ``multipath``;
    ``wl`` the workload fields of :mod:`bench.gen`."""
    dev = lambda a, dt=jnp.int32: jnp.asarray(a, dt)
    j = {k: dev(tables[k]) for k in ("conn", "tf_next", "tf_dep", "inj_next",
                                     "inj_dep", "first_direct")}
    j.update({k: dev(wl[k]) for k in ("src", "dst", "size", "t_inject",
                                      "flow", "seq")})
    j["is_eleph"] = dev(wl["is_eleph"], jnp.bool_)
    P = int(np.asarray(wl["src"]).shape[0])
    num_flows = int(max(np.asarray(wl["flow"]).max() + 1, 1)) if P else 1
    out = _simulate_jit_ref(j, cfg, num_slices,
                            tables["multipath"] == "packet", num_flows)
    return {k: np.asarray(v) for k, v in out.items()}


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4))
def _simulate_jit_ref(j, cfg: RefConfig, num_slices: int, per_packet_mp: bool,
                      num_flows: int):
    T, N, U = j["conn"].shape
    P = j["src"].shape[0]
    pid = jnp.arange(P, dtype=jnp.int32)
    NKEY = N * (N + 1)

    state = dict(
        loc=jnp.full((P,), NOT_INJECTED, jnp.int32),
        nxt=jnp.full((P,), -1, jnp.int32),
        dep=jnp.zeros((P,), jnp.int32),
        relook=jnp.zeros((P,), bool),
        nhops=jnp.zeros((P,), jnp.int32),
        t_del=jnp.full((P,), -1, jnp.int32),
        block_until=jnp.zeros((N, T), jnp.int32),
        max_seq=jnp.full((num_flows,), -1, jnp.int32),
        reorder=jnp.zeros((), jnp.int32),
    )

    def mp_hash(t):
        base = pid if per_packet_mp else j["flow"]
        salt = jnp.uint32(t) * jnp.uint32(0x9E3779B9) if per_packet_mp else jnp.uint32(0)
        return _hash32(base.astype(jnp.uint32) + salt)

    def enqueue_checks(s, t, arrived, off):
        dep_abs = t + off
        qb = (s["loc"] * (2 * T) + dep_abs % (2 * T))
        waiting = (s["loc"] >= 0) & (s["dep"] > t)
        occ = jax.ops.segment_sum(jnp.where(waiting, j["size"], 0),
                                  jnp.where(waiting, s["loc"] * (2 * T) + s["dep"] % (2 * T), N * 2 * T),
                                  num_segments=N * 2 * T + 1)[:N * 2 * T]
        q_occ = occ[jnp.clip(qb, 0, N * 2 * T - 1)]
        limit = jnp.minimum(cfg.slice_bytes, cfg.congestion_threshold)
        full = arrived & (off > 0) & (q_occ > limit)
        if cfg.cc_detect:
            defer = full
            s["relook"] = s["relook"] | defer
            s["dep"] = jnp.where(defer, t + 1, s["dep"])
            if cfg.pushback:
                blk_t = dep_abs % T
                upd = jnp.where(defer, t + T, 0)
                s["block_until"] = s["block_until"].at[j["dst"], blk_t].max(upd)
        return s, full

    def step(state, t):
        s = dict(state)
        h = mp_hash(t)

        ready = (j["t_inject"] <= t) & (s["loc"] == NOT_INJECTED)
        nxt_i, off_i = _lookup(j["inj_next"], j["inj_dep"], t, j["src"], j["dst"], h)
        if cfg.flow_pausing:
            fd = j["first_direct"][t % T, j["src"], j["dst"]]
            use_direct = j["is_eleph"] & (fd >= 0)
            nxt_i = jnp.where(use_direct, j["dst"], nxt_i)
            off_i = jnp.where(use_direct, fd, off_i)
        if cfg.pushback:
            blocked = s["block_until"][j["dst"], (t + off_i) % T] > t
        else:
            blocked = jnp.zeros((ready.shape[0],), bool)
        inject = ready & ~blocked
        s["loc"] = jnp.where(inject, j["src"], s["loc"])
        s["nxt"] = jnp.where(inject, nxt_i, s["nxt"])
        s["dep"] = jnp.where(inject, t + off_i, s["dep"])
        s, _ = enqueue_checks(s, t, inject, jnp.where(inject, off_i, 0))
        n_blocked = jnp.sum(ready & blocked)

        redo = s["relook"] & (s["loc"] >= 0) & (s["dep"] == t)
        nxt_r, off_r = _lookup(j["tf_next"], j["tf_dep"], t - cfg.table_lag,
                               jnp.clip(s["loc"], 0, N - 1), j["dst"], h)
        s["nxt"] = jnp.where(redo, nxt_r, s["nxt"])
        s["dep"] = jnp.where(redo, t + off_r, s["dep"])
        s["relook"] = s["relook"] & ~redo

        caps = _build_caps(j["conn"][t % T], cfg, N)
        used = jnp.zeros((NKEY,), jnp.int32)
        on_switch = (s["loc"] >= 0) & (s["dep"] > t) & \
                    ((s["dep"] - t <= cfg.offload_horizon) if cfg.offload else True)
        buf_now = jax.ops.segment_sum(jnp.where(on_switch, j["size"], 0),
                                      jnp.clip(s["loc"], 0, N - 1) * jnp.where(s["loc"] >= 0, 1, 0),
                                      num_segments=N)

        for _hop in range(cfg.hops_per_slice):
            want = (s["loc"] >= 0) & (s["dep"] == t) & (s["nxt"] >= 0) & \
                   (s["nhops"] < cfg.max_hops)
            if cfg.pushback:
                need_buf = want & (s["nxt"] < N) & (s["nxt"] != j["dst"])
                room = jnp.maximum(cfg.switch_buffer - buf_now, 0)
                adm_rx, _ = _group_admit(jnp.clip(s["nxt"], 0, N - 1),
                                         j["size"], need_buf, room, N)
                want &= adm_rx | ~need_buf
            key = jnp.clip(s["loc"], 0, N - 1) * (N + 1) + jnp.clip(s["nxt"], 0, N)
            admitted, consumed = _group_admit(key, j["size"], want, caps - used, NKEY)
            used = used + consumed
            is_elec = admitted & (s["nxt"] == N)
            moved = admitted & ~is_elec
            newloc = jnp.where(moved, s["nxt"], s["loc"])
            at_dst = (moved & (s["nxt"] == j["dst"])) | is_elec
            s["t_del"] = jnp.where(at_dst, jnp.where(is_elec, t + 1, t), s["t_del"])
            dseq = jnp.where(at_dst, j["seq"], -1)
            prev_max = s["max_seq"][j["flow"]]
            s["reorder"] = s["reorder"] + jnp.sum(at_dst & (j["seq"] < prev_max))
            s["max_seq"] = s["max_seq"].at[j["flow"]].max(dseq)
            s["loc"] = jnp.where(at_dst, DELIVERED, newloc)
            s["nhops"] = s["nhops"] + admitted.astype(jnp.int32)
            in_transit = moved & ~at_dst
            nxt_t, off_t = _lookup(j["tf_next"], j["tf_dep"], t - cfg.table_lag,
                                   jnp.clip(s["loc"], 0, N - 1), j["dst"], h)
            s["nxt"] = jnp.where(in_transit, nxt_t, s["nxt"])
            s["dep"] = jnp.where(in_transit, t + off_t, s["dep"])
            arr_sz = jax.ops.segment_sum(jnp.where(in_transit, j["size"], 0),
                                         jnp.clip(s["loc"], 0, N - 1), num_segments=N)
            buf_now = buf_now + arr_sz
            overflow = in_transit & (buf_now[jnp.clip(s["loc"], 0, N - 1)] > cfg.switch_buffer)
            if cfg.pushback:
                upd = jnp.where(overflow, t + T, 0)
                s["block_until"] = s["block_until"].at[
                    j["dst"], s["dep"] % T].max(upd)
            s["loc"] = jnp.where(overflow, DROPPED, s["loc"])
            s, _full = enqueue_checks(s, t, in_transit & ~overflow,
                                      jnp.where(in_transit, off_t, 0))

        missed = (s["loc"] >= 0) & (s["dep"] == t)
        miss_cnt = jnp.sum(missed)
        if cfg.cc_detect:
            s["relook"] = s["relook"] | missed
            s["dep"] = jnp.where(missed, t + 1, s["dep"])
        else:
            s["dep"] = jnp.where(missed, t + T, s["dep"])
        if cfg.pushback:
            upd = jnp.where(missed, t + T, 0)
            s["block_until"] = s["block_until"].at[j["dst"], t % T].max(upd)

        waiting = (s["loc"] >= 0) & (s["dep"] > t)
        horizon_ok = (s["dep"] - t <= cfg.offload_horizon) if cfg.offload \
            else jnp.ones_like(waiting)
        seg = jnp.where(waiting, s["loc"], N)
        on_sw = jax.ops.segment_sum(jnp.where(waiting & horizon_ok, j["size"], 0),
                                    seg, num_segments=N + 1)[:N]
        off_sw = jax.ops.segment_sum(jnp.where(waiting & ~horizon_ok, j["size"], 0),
                                     seg, num_segments=N + 1)[:N]
        stats = dict(
            delivered_bytes=jnp.sum(jnp.where(s["t_del"] == t, j["size"], 0)),
            dropped=jnp.sum(s["loc"] == DROPPED),
            buf_bytes=on_sw, offl_bytes=off_sw,
            blocked_inj=n_blocked, slice_miss=miss_cnt,
        )
        return s, stats

    def idle(s, t):
        none = jnp.zeros((), jnp.int32)
        stats = dict(
            delivered_bytes=jnp.sum(jnp.where(s["t_del"] == t, j["size"], 0)),
            dropped=jnp.sum(s["loc"] == DROPPED),
            buf_bytes=jnp.zeros((N,), jnp.int32),
            offl_bytes=jnp.zeros((N,), jnp.int32),
            blocked_inj=none, slice_miss=none,
        )
        return s, stats

    def step_or_idle(s, t):
        live = jnp.any((s["loc"] >= 0) | (s["loc"] == NOT_INJECTED))
        return jax.lax.cond(live, step, idle, s, t)

    final, ys = jax.lax.scan(step_or_idle, state,
                             jnp.arange(num_slices, dtype=jnp.int32))
    return dict(
        t_deliver=final["t_del"], loc_final=final["loc"], nhops=final["nhops"],
        delivered_bytes=ys["delivered_bytes"], dropped=ys["dropped"],
        buf_bytes=ys["buf_bytes"], offl_bytes=ys["offl_bytes"],
        blocked_inj=ys["blocked_inj"], slice_miss=ys["slice_miss"],
        reorder_cnt=final["reorder"],
    )
