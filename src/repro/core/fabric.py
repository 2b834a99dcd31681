"""The OpenOptics data plane as a JAX program (paper §5).

The paper re-architects switch queue management (P4 on Tofino2) to execute
time-flow tables: calendar queues per egress port hold packets until their
departure slice, a queue-occupancy estimate drives congestion detection,
push-back pauses hosts, and buffers can be offloaded to hosts. Here the whole
data plane is a single ``lax.scan`` over time slices with packets as
structure-of-arrays tensors — fully ``jit``-able, so the simulator itself is a
JAX workload (and the per-packet table lookup has a Pallas TPU kernel,
``repro.kernels.time_flow_lookup``, selected with ``FabricConfig.lookup_impl``).

Semantics per slice ``t`` (mirroring §5.1):
  1. hosts inject packets whose time has come (unless push-back blocks them;
     elephant flows under flow pausing wait for a direct circuit instead);
  2. packets whose calendar queue becomes active (``dep == t``) transmit over
     their circuit, subject to per-circuit capacity ``slice_bytes`` — the
     admissible data amount of the slice. Packets may chain up to
     ``hops_per_slice`` cut-through hops within the slice (Opera-style);
  3. packets that do not fit miss the slice: with congestion detection they
     are deferred and re-looked-up next slice (HOHO/UCMP-style); without it
     they stall a full schedule cycle in the paused queue (paper §5.2);
     push-back additionally blocks the source slice bucket for one cycle;
  4. switch buffer accounting (with optional offloading of far-future
     calendar queues to hosts) decides drops.

An "electrical" egress (peer id == N) models the packet-switched fabric of
hybrid architectures (c-Through) and the Clos baseline: always available,
per-node capacity ``elec_bytes``, one-slice transit delay.

Hot-path architecture (ISSUE 1; bit-identical to the reference formulation
kept in ``tests/fabric_ref.py``):

* **Calendar-queue occupancy is carried in the scan state** as a flat
  ``[N * 2T]`` byte map instead of being rebuilt with a ``segment_sum`` at
  every congestion check. Packets enter their (node, dep mod 2T) bucket when
  they enqueue with a future departure, move buckets when deferred, and leave
  the map in the slice their queue activates. Per-node buffer totals and the
  per-slice ``buf/offl`` statistics are row/column sums of this map.
* **Each phase runs on a compact view of the packet vector.** The active
  population (injection + re-lookup candidates; per-hop transmission
  candidates) is compacted in index order with cumsum + searchsorted (no
  scatter), the whole phase — admission sort, table lookup, occupancy and
  reorder updates — executes at the view width (tiers of 2048 / 8192), and
  the touched fields are scattered back. ``lax.cond`` picks the tier from
  the live count and falls back to the full-width formulation above the
  largest tier; empty phases reduce to the identity. FIFO admission is
  order-preserving under compaction, so results are unchanged.
* **Provably-rejected backlog is dropped from later hops.** Admission is a
  cumulative-prefix cut per (loc, nxt) group and per-group capacity only
  shrinks within a slice, so a packet positioned at or after the first
  rejected index of its group can never be admitted in a later hop. Hop 0
  records the minimum rejected index per group; hops >= 1 only re-sort the
  cut-through continuations. This is what makes the packet vector
  effectively *sorted once per slice*. The cuts start every slice empty,
  so the filter is the identity until a group (or, under push-back, a
  receiver) holds one: hop 0 never reads them, and a later hop gathers
  each packet's cut (scope ``fabric/hop/backlog_filter/gather``) only
  under a ``lax.cond`` on "any cut held". Under push-back the capacity
  argument is weakened (an rx candidate that later flips to rx-rejected
  removes its bytes from successors' capacity prefixes), but two rx-aware
  cuts survive and are applied instead. Receivers' rx rejections are
  themselves a monotone FIFO prefix cut (room shrinks at least as fast as
  any candidate's rx prefix), so rx-subject candidates at-or-after their
  receiver's first rx rejection are dropped. And for the capacity cut,
  the only bytes that can ever *leave* a candidate's prefix are those of
  an earlier same-group member that was rx-admitted but capacity-rejected
  (it may flip to rx-rejected later); so an rx-exempt candidate
  (electrical egress, or delivering directly to its destination) in a
  group with no such "rescuable" predecessor is provably rejected for the
  rest of the slice, and later hops cut strictly *after* the group's
  first marked index — the marked packet itself stays in the admission
  sort as the byte anchor that keeps every successor's prefix above
  capacity. rx-subject members are never capacity-cut (their bytes
  participate in other candidates' rx prefixes). (ISSUE 5/6;
  bit-identity vs the unfiltered reference enforced by the fabric
  goldens, including a mixed rx/capacity-pressure case.)
* **Admission itself is a swappable backend** (``FabricConfig.admit_impl``):
  the XLA stable-sort + segmented-prefix formulation, or the sort-free
  Pallas kernel (:mod:`repro.kernels.admission`) that carries a per-key
  byte accumulator across packet tiles — bit-identical, selected exactly
  like ``lookup_impl``.
* **The injection and deferred-re-lookup table lookups are fused** into one
  gather over stacked (injection, transit) tables; the transit lookup inside
  the hop body is the third and only other lookup site.
* **Per-slice circuit capacities are precompiled** for the whole schedule
  cycle (``[T, N*(N+1)]``) outside the scan.
"""
from __future__ import annotations

import dataclasses
import functools

import numpy as np
import jax
import jax.numpy as jnp

from . import tracing
from .routing import CompiledRouting, first_direct_offsets
from .telemetry import (TELE_KEYS, TelemetryConfig, TelemetryCounters,
                        counters_from_out)
from .topology import Schedule
from ..kernels.admission import admission_admit
from ..kernels.time_flow_lookup import time_flow_lookup

__all__ = ["FabricConfig", "Workload", "FabricTables", "simulate",
           "simulate_sharded", "simulate_fleet", "SimResult", "FabricState",
           "init_state", "ingest", "step_slices", "finalize",
           "simulate_incremental"]

NOT_INJECTED = -1
DELIVERED = -2
DROPPED = -3


@dataclasses.dataclass(frozen=True)
class FabricConfig:
    """Static fabric parameters (hashable; closed over by the jitted step).

    slice_bytes: admissible bytes per circuit per slice — the time-slice
        capacity quantum (default: 100 Gbps x 6 us).
    elec_bytes: per-node electrical egress capacity per slice; > 0 enables
        the packet-switched fabric of hybrid architectures (peer id == N).
    switch_buffer: per-switch buffer bound; arrivals beyond it drop (and
        push the sender back when ``pushback``).
    hops_per_slice: cut-through chaining bound within one slice (Opera).
    max_hops: lifetime hop bound per packet.
    cc_detect: congestion detection (§5.2) — packets that miss their slice
        or hit a full calendar queue defer one slice and re-look-up, instead
        of stalling a full schedule cycle.
    pushback: traffic push-back (§5.2) — congested queues block their source
        slice bucket for a cycle; rejected transmissions defer at the sender.
    offload / offload_horizon: buffer offloading (§5.2) — only the next
        ``offload_horizon`` calendar queues stay switch-resident, the rest
        count as host-offloaded bytes.
    flow_pausing: hold elephant flows at the host until a direct circuit to
        their destination appears (§5.2).
    congestion_threshold: classic CC byte threshold per calendar queue
        (effective limit is ``min(slice_bytes, congestion_threshold)``).
    lookup_impl: per-packet table-lookup backend — "jnp" (pure gathers,
        default), "pallas" (TPU kernel), "pallas-interpret" (kernel body on
        CPU for validation). All three are bit-identical; see
        :mod:`repro.kernels.time_flow_lookup`.
    admit_impl: queue-admission backend — "xla" (stable-sort + segmented
        prefix-sum, default), "pallas" (the sort-free TPU kernel),
        "pallas-interpret" (kernel body on CPU for validation). All three
        are bit-identical; see :mod:`repro.kernels.admission`. Every
        admission site routes through this knob: the per-slice capacity cut
        and the push-back receiver-buffer cut in :func:`_make_step`, the
        epoch scan of :func:`repro.core.reconfigure.reconfigure`, and the
        failure-masked capacity recompute (``failures=``) — they all call
        :func:`_admit`.

    Failure state is *data*, not static config: per-slice fault masks
    (:class:`repro.core.failures.FailureMasks`) enter through
    :func:`simulate`'s ``failures`` argument and are threaded through the
    jitted step; the step only branches on their presence, so failure-free
    runs trace the exact pre-failure program. Control-plane state
    (:class:`repro.core.controlplane.ControlMasks` — per-ToR clock-skew
    phase offsets and guard-band misses) enters the same way through the
    ``control`` argument, and versioned time-flow tables (mixed-version
    epochs during a staggered install) through
    :func:`repro.core.reconfigure.reconfigure`'s install machinery; both
    follow the same presence-gated rule, so zero-skew runs trace the
    exact pre-control program.
    """

    slice_bytes: int = 75_000        # 100 Gbps x 6 us, per circuit per slice
    elec_bytes: int = 0              # electrical egress capacity per node/slice
    switch_buffer: int = 64 << 20    # Tofino2: 64 MB
    hops_per_slice: int = 4
    max_hops: int = 16
    cc_detect: bool = True           # congestion detection (§5.2)
    pushback: bool = False           # traffic push-back (§5.2)
    offload: bool = False            # buffer offloading (§5.2)
    offload_horizon: int = 2         # switch keeps N calendar queues; rest on hosts
    flow_pausing: bool = False       # hold elephants for direct circuits (§5.2)
    congestion_threshold: int = 1 << 30  # classic CC threshold, bytes per queue
    lookup_impl: str = "jnp"         # "jnp" | "pallas" (TPU) | "pallas-interpret"
    admit_impl: str = "xla"          # "xla" | "pallas" (TPU) | "pallas-interpret"


@dataclasses.dataclass
class Workload:
    """Packets (cells) to simulate, structure-of-arrays."""

    src: np.ndarray       # [P] i32
    dst: np.ndarray       # [P] i32
    size: np.ndarray      # [P] i32 bytes
    t_inject: np.ndarray  # [P] i32 slice index
    flow: np.ndarray      # [P] i32 flow id (dense, < F)
    seq: np.ndarray       # [P] i32 sequence within flow
    is_eleph: np.ndarray  # [P] bool

    @property
    def num_packets(self) -> int:
        return int(self.src.shape[0])

    @property
    def num_flows(self) -> int:
        return int(self.flow.max()) + 1 if self.num_packets else 0


@dataclasses.dataclass
class FabricTables:
    """Dense deployed state: the optical schedule + compiled time-flow tables."""

    conn: np.ndarray       # [T, N, U]
    tf_next: np.ndarray    # [Tr, N, D, K]
    tf_dep: np.ndarray
    inj_next: np.ndarray
    inj_dep: np.ndarray
    first_direct: np.ndarray  # [T, N, D] offset to next direct circuit (-1 none)
    multipath: str = "packet"

    @classmethod
    def build(cls, sched: Schedule, routing: CompiledRouting) -> "FabricTables":
        return cls(
            conn=sched.conn,
            tf_next=routing.tf_next, tf_dep=routing.tf_dep,
            inj_next=routing.inj_next, inj_dep=routing.inj_dep,
            first_direct=_first_direct(sched),
            multipath=routing.multipath,
        )


def _first_direct(sched: Schedule) -> np.ndarray:
    """first_direct[t, n, d]: slices to wait at node n (arriving slice t) for a
    direct circuit n -> d; -1 if the schedule never provides one."""
    return first_direct_offsets(sched)


@dataclasses.dataclass
class SimResult:
    t_deliver: np.ndarray     # [P] slice of delivery (-1 undelivered)
    loc_final: np.ndarray     # [P]
    nhops: np.ndarray         # [P]
    delivered_bytes: np.ndarray  # [S] per slice
    dropped: np.ndarray       # [S] cumulative dropped-packet count at slice end
    buf_bytes: np.ndarray     # [S, N] switch-resident buffer per node
    offl_bytes: np.ndarray    # [S, N] host-offloaded buffer per node
    blocked_inj: np.ndarray   # [S] injections deferred by push-back
    slice_miss: np.ndarray    # [S] packets that missed their slice
    reorder_cnt: np.ndarray   # scalar: out-of-order deliveries
    # per-ToR per-slice counter frames when simulate ran with telemetry=
    # (None otherwise; see repro.core.telemetry)
    telemetry: "TelemetryCounters | None" = None


# ---------------------------------------------------------------------------
# jitted machinery
# ---------------------------------------------------------------------------

def _hash32(x: jnp.ndarray) -> jnp.ndarray:
    x = x.astype(jnp.uint32)
    x = (x ^ (x >> 16)) * jnp.uint32(0x7FEB352D)
    x = (x ^ (x >> 15)) * jnp.uint32(0x846CA68B)
    return x ^ (x >> 16)


def _select_slot(row_n, row_d, hashv):
    """Choose a multipath slot by hash over the (contiguous) valid slots."""
    nvalid = jnp.sum(row_n >= 0, axis=-1)    # [P]
    slot = (hashv % jnp.maximum(nvalid, 1).astype(jnp.uint32)).astype(jnp.int32)
    nxt = jnp.take_along_axis(row_n, slot[:, None], axis=-1)[:, 0]
    off = jnp.take_along_axis(row_d, slot[:, None], axis=-1)[:, 0]
    return nxt, off


def _lookup(next_tbl, dep_tbl, t, node, dst, hashv, impl: str = "jnp"):
    """Time-flow table lookup: match (arrival slice, dst) at ``node``.

    ``impl="jnp"`` is the pure-gather formulation; ``"pallas"`` routes through
    the :mod:`repro.kernels.time_flow_lookup` TPU kernel (compiled lowering),
    ``"pallas-interpret"`` runs the same kernel body in interpret mode (CPU
    validation). All three produce bit-identical outputs.
    """
    Tr = next_tbl.shape[0]
    tm = t % Tr
    if impl != "jnp":
        return time_flow_lookup(next_tbl[tm], dep_tbl[tm], node, dst, hashv,
                                interpret=(impl != "pallas"))
    row_n = next_tbl[tm, node, dst]          # [P, K]
    row_d = dep_tbl[tm, node, dst]
    return _select_slot(row_n, row_d, hashv)


def _group_admit(key, size, want, cap_left, num_keys):
    """Deterministic FIFO admission under per-key capacity (XLA backend:
    stable sort by key + segmented prefix-sum over the sorted order).

    Packets are processed in index order within each key group; a packet is
    admitted if the group's running byte count still fits ``cap_left[key]``.
    Returns (admitted mask, bytes-consumed-per-key).
    """
    P = key.shape[0]
    key_eff = jnp.where(want, key, num_keys)  # park inactive in sentinel group
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


def _group_admit_impl(key, size, want, cap_left, num_keys, impl: str):
    """The swappable admission backend boundary: ``"xla"`` is the
    stable-sort formulation above; ``"pallas"``/``"pallas-interpret"`` run
    the sort-free segmented-prefix kernel
    (:func:`repro.kernels.admission.admission_admit` — bit-identical)."""
    if impl == "xla":
        return _group_admit(key, size, want, cap_left, num_keys)
    return admission_admit(key, size, want, cap_left, num_keys=num_keys,
                           interpret=(impl != "pallas"))


# Compact-path population bounds: when at most this many packets are active in
# a phase, the phase runs on a gathered C-sized view of the packet vector
# (sorting/scattering C elements) instead of all P. ``lax.cond`` falls back to
# the full-width formulation above the bound, so results are identical.
ADMIT_C = 8192
SMALL_C = 4096


def _compact_idx(mask, C):
    """Indices of the first C True entries of ``mask`` in index order
    (== len(mask) for fill slots), via cumsum + searchsorted — no scatter."""
    cm = jnp.cumsum(mask.astype(jnp.int32))
    return jnp.searchsorted(cm, jnp.arange(1, C + 1, dtype=jnp.int32))


def _group_admit_small(key, size, want, cap_left, num_keys, C, impl="xla"):
    """FIFO admission on the compacted want-set: identical results to
    :func:`_group_admit` whenever ``sum(want) <= C`` (compaction preserves
    index order, so per-group FIFO prefixes are unchanged)."""
    P = key.shape[0]
    idx = _compact_idx(want, C)
    ok = idx < P
    ic = jnp.clip(idx, 0, P - 1)
    kc = jnp.where(ok, key[ic], num_keys)
    sc = jnp.where(ok, size[ic], 0)
    adm_c, used = _group_admit_impl(kc, sc, ok, cap_left, num_keys, impl)
    admitted = jnp.zeros((P,), bool).at[idx].set(adm_c, mode="drop")
    return admitted, used


def _admit(key, size, want, cap_left, num_keys, C=ADMIT_C, impl="xla",
           axis=None, num_shards=1):
    """Dispatch between the compact and full admission paths; ``impl``
    (``FabricConfig.admit_impl``) selects the backend inside both.

    ``axis`` (a shard_map mesh axis name) switches to the cross-shard
    formulation: packets are partitioned over the axis in contiguous
    global-index blocks, so a local packet's *global* FIFO byte prefix in
    its admission group is its local prefix plus the wanted bytes of all
    lower-indexed shards — a per-key offset from one all_gather of
    per-shard per-key byte totals (the static ``[num_shards, num_keys]``
    exchange buffer; :func:`repro.distributed.collectives
    .shard_group_offsets`). Shifting the capacities down by that offset
    turns any local backend into the exact global admission — including
    the Pallas kernel, which dispatches under shard_map unchanged."""
    P = key.shape[0]
    if axis is not None:
        from ..distributed.collectives import shard_group_offsets
        local_bytes = jax.ops.segment_sum(
            jnp.where(want, size, 0), jnp.where(want, key, num_keys),
            num_segments=num_keys + 1)[:num_keys]
        with jax.named_scope(tracing.EXCHANGE):
            offs = shard_group_offsets(local_bytes, axis, num_shards)
        admitted, used = _group_admit_impl(
            key, size, want, cap_left - offs, num_keys, impl)
        with jax.named_scope(tracing.EXCHANGE):
            return admitted, jax.lax.psum(used, axis)
    if P <= C:
        return _group_admit_impl(key, size, want, cap_left, num_keys, impl)
    return jax.lax.cond(
        jnp.sum(want) <= C,
        lambda _: _group_admit_small(key, size, want, cap_left, num_keys, C,
                                     impl),
        lambda _: _group_admit_impl(key, size, want, cap_left, num_keys,
                                    impl),
        None)


def _scatter_add_masked(target, indices, values, mask, C=SMALL_C):
    """``target.at[indices].add(where(mask, values, 0))`` with a compact fast
    path for sparse masks (same sum, so bit-identical)."""
    P = indices.shape[0]
    if P <= C:
        return target.at[indices].add(jnp.where(mask, values, 0))

    def small(tgt):
        idx = _compact_idx(mask, C)
        ok = idx < P
        ic = jnp.clip(idx, 0, P - 1)
        return tgt.at[jnp.where(ok, indices[ic], 0)].add(
            jnp.where(ok, values[ic], 0))

    def big(tgt):
        return tgt.at[indices].add(jnp.where(mask, values, 0))

    return jax.lax.cond(jnp.sum(mask) <= C, small, big, target)


def _build_caps_all(conn, cfg: FabricConfig, N: int):
    """Per-circuit capacity for every slice of the cycle, keyed
    loc*(N+1)+peer; key loc*(N+1)+N is the electrical egress. Precomputed
    once per ``simulate`` call ([T, N*(N+1)]) instead of per slice."""
    T, _, U = conn.shape
    caps = jnp.zeros((T, N * (N + 1)), jnp.int32)
    rows = jnp.arange(N, dtype=jnp.int32)[None, :]
    trows = jnp.arange(T)[:, None]
    for k in range(U):
        peer = conn[:, :, k]                                   # [T, N]
        keyk = rows * (N + 1) + jnp.where(peer >= 0, peer, N)  # dark -> elec key
        add = jnp.where(peer >= 0, jnp.int32(cfg.slice_bytes), 0)
        caps = caps.at[trows, keyk].add(add)
    caps = caps.at[:, jnp.arange(N) * (N + 1) + N].add(jnp.int32(cfg.elec_bytes))
    return caps


def simulate(tables: FabricTables, wl: Workload, cfg: FabricConfig,
             num_slices: int, failures=None, control=None,
             telemetry: TelemetryConfig | None = None) -> SimResult:
    """Run the fabric for ``num_slices`` slices.

    Args:
        tables: deployed state — the optical schedule ``conn`` plus compiled
            time-flow tables (``[T, N, D, K]``; see
            :class:`repro.core.routing.CompiledRouting` for the layout).
        wl: the packet workload (structure-of-arrays; see :class:`Workload`).
        cfg: static fabric parameters. ``cfg.lookup_impl`` selects the
            per-packet table-lookup backend ("jnp" gathers, "pallas" TPU
            kernel, "pallas-interpret" CPU validation — all bit-identical).
        num_slices: slices to run (the schedule cycle wraps as needed).
        failures: optional :class:`repro.core.failures.FailureMasks`
            covering the run ([num_slices, N, N] link capacities +
            [num_slices, N] ToR liveness). Dead/degraded circuits admit
            less (nothing, when dead), so their packets miss the slice and
            re-enqueue through the §5.2 machinery; down ToRs neither
            inject nor terminate electrical transfers. ``None`` (default)
            traces exactly the failure-free program.
        control: optional :class:`repro.core.controlplane.ControlMasks`
            covering the run. A ToR skewed by whole slices
            (``phase_off``) consults its time-flow tables at its *local*
            slice, so it injects into the wrong slice's circuit (live
            only if the schedule happens to provide it — otherwise the
            packet misses and re-enqueues via the §5.2 deferral path); a
            ToR whose residual offset exceeds the guard band
            (``skew_miss``) misses its optical transmit windows
            outright that slice (the asynchronous electrical fabric is
            exempt). Requires ``cfg.lookup_impl == "jnp"`` (per-ToR
            local slices make the table lookup per-packet in time).
            ``None`` (default) traces exactly the zero-skew program.
        telemetry: optional :class:`repro.core.telemetry.TelemetryConfig`
            (static, like ``cfg``). When set, per-ToR per-slice counters
            accumulate in the scan carry and come back as
            ``SimResult.telemetry``; every non-telemetry field is
            unchanged. ``None`` (default) traces exactly the
            pre-telemetry program — the same presence rule as
            ``failures`` / ``control``.

    Everything inside is jitted; re-compilation happens per (packet count,
    table shapes, config). For a loop that *recompiles the tables on-device
    mid-run*, see :func:`repro.core.reconfigure.reconfigure` — it reuses this
    module's per-slice step via :func:`_make_step` with tables swapped in
    from the scan carry.
    """
    _check_impls(cfg)
    T, N, U = tables.conn.shape
    dev = lambda a, dt=jnp.int32: jnp.asarray(a, dt)
    with tracing.span("run.to_device"):
        j = dict(
            conn=dev(tables.conn), tf_next=dev(tables.tf_next), tf_dep=dev(tables.tf_dep),
            inj_next=dev(tables.inj_next), inj_dep=dev(tables.inj_dep),
            first_direct=dev(tables.first_direct),
            src=dev(wl.src), dst=dev(wl.dst), size=dev(wl.size),
            t_inject=dev(wl.t_inject), flow=dev(wl.flow), seq=dev(wl.seq),
            is_eleph=dev(wl.is_eleph, jnp.bool_),
        )
        if failures is not None:
            failures.validate(num_slices, N)
            j["link_cap"] = dev(failures.link_cap, jnp.float32)
            j["node_ok"] = dev(failures.node_ok, jnp.bool_)
        if control is not None:
            if cfg.lookup_impl != "jnp":
                raise ValueError(
                    "control-plane masks need lookup_impl='jnp': per-ToR local "
                    f"slices make lookups per-packet in time (got "
                    f"{cfg.lookup_impl!r})")
            control.validate(num_slices, N)
            j["phase_off"] = dev(control.phase_off)
            j["skew_miss"] = dev(control.skew_miss, jnp.bool_)
    per_packet_mp = tables.multipath == "packet"
    with tracing.span("run.dispatch"):
        out = _simulate_jit(
            j, cfg, num_slices, per_packet_mp,
            int(max(wl.flow.max() + 1, 1)) if wl.num_packets else 1,
            telemetry)
    with tracing.span("run.device_wait"):
        out = jax.block_until_ready(out)
    with tracing.span("run.result_copy"):
        out = {k: np.asarray(v) for k, v in out.items()}
        tele = counters_from_out(out, telemetry)
    return SimResult(**out, telemetry=tele)


def _init_state(j, num_flows: int, telemetry: TelemetryConfig | None = None):
    """Fresh per-packet scan state for the workload in ``j`` (all packets
    un-injected, empty calendar queues). With ``telemetry`` the per-slice
    counter accumulators join the carry (reset by the step each slice)."""
    T, N, U = j["conn"].shape
    P = j["src"].shape[0]
    NQ = N * 2 * T
    st = dict(
        loc=jnp.full((P,), NOT_INJECTED, jnp.int32),
        nxt=jnp.full((P,), -1, jnp.int32),
        dep=jnp.zeros((P,), jnp.int32),
        relook=jnp.zeros((P,), bool),
        nhops=jnp.zeros((P,), jnp.int32),
        t_del=jnp.full((P,), -1, jnp.int32),
        block_until=jnp.zeros((N, T), jnp.int32),  # [dst, slice bucket]
        max_seq=jnp.full((num_flows,), -1, jnp.int32),
        reorder=jnp.zeros((), jnp.int32),
        occ=jnp.zeros((NQ,), jnp.int32),  # calendar-queue occupancy [N * 2T]
    )
    if telemetry is not None:
        st.update(
            _tin=jnp.zeros((N,), jnp.int32),    # injected bytes per src ToR
            _tdef=jnp.zeros((N,), jnp.int32),   # deferred bytes per switch
            _tdrop=jnp.zeros((N,), jnp.int32),  # dropped bytes per switch
            _thwm=jnp.zeros((N,), jnp.int32),   # switch-buffer high water
        )
    return st


def _make_step(j, cfg: FabricConfig, per_packet_mp: bool, num_flows: int,
               axis=None, num_shards=1, batched=False,
               telemetry: TelemetryConfig | None = None):
    """Build the per-slice ``step(state, t) -> (state, stats)`` function over
    the arrays in ``j`` (schedule + tables + workload).

    Called at trace time; ``j`` may hold concrete device arrays *or tracers* —
    :mod:`repro.core.reconfigure` passes freshly recompiled tables from its
    epoch carry, which is what lets it hot-swap routing mid-run without
    re-jitting. Everything derived here (per-slice capacities, the stacked
    injection/transit lookup tables) is recomputed from ``j`` per trace.

    With ``axis`` (a shard_map mesh axis name; see :func:`simulate_sharded`)
    the same step runs *sharded*: the per-packet arrays in ``j`` and the
    per-packet state are this shard's contiguous global-index block, the
    per-ToR aggregates (occupancy map, backlog views, block_until, max_seq)
    stay replicated and are reconciled through
    :mod:`repro.distributed.collectives` exchange primitives at every update
    site (psum of scatter-add deltas, pmin of backlog cuts, pmax of
    block_until / max_seq), and every admission routes through
    :func:`_admit`'s cross-shard offset exchange. Data-dependent ``lax.cond``
    skips are disabled (their predicates are shard-local, so shards could
    diverge around the collectives); each skipped branch is a semantic
    identity, so the sharded program stays bit-identical to the
    single-device one — which the multi-device differential suite asserts.
    """
    assert not ("tf_next_v" in j and axis is not None), \
        "versioned installs come from reconfigure, which vmaps, not shards"
    T, N, U = j["conn"].shape
    P = j["src"].shape[0]            # the local block width under sharding
    if axis is None:
        shard = None
        pid = jnp.arange(P, dtype=jnp.int32)
        PG = P
    else:
        shard = jax.lax.axis_index(axis)
        # global packet ids: shard d owns global indices [d*P, (d+1)*P)
        pid = (shard * P + jnp.arange(P)).astype(jnp.int32)
        PG = P * num_shards          # global (padded) packet count
    NKEY = N * (N + 1)
    T2 = 2 * T                       # calendar-queue ring: dep in (t, t + 2T)
    limit = jnp.minimum(cfg.slice_bytes, cfg.congestion_threshold)

    # Replicated-state reconciliation points (identities when unsharded):
    # every update of a replicated aggregate is exchanged before its next
    # read so all shards keep bit-identical copies.
    def gsum(x):
        if axis is None:
            return x
        with jax.named_scope(tracing.EXCHANGE):
            return jax.lax.psum(x, axis)

    def gmin(x):
        if axis is None:
            return x
        with jax.named_scope(tracing.EXCHANGE):
            return jax.lax.pmin(x, axis)

    def gmax(x):
        if axis is None:
            return x
        with jax.named_scope(tracing.EXCHANGE):
            return jax.lax.pmax(x, axis)

    def upd_add(target, *updates):
        """Apply masked scatter-adds to a replicated aggregate; sharded,
        the local delta is accumulated separately and psum-reconciled so
        every shard applies the same global update."""
        if axis is None:
            for idx, vals, mask in updates:
                target = _scatter_add_masked(target, idx, vals, mask)
            return target
        d = jnp.zeros_like(target)
        for idx, vals, mask in updates:
            d = _scatter_add_masked(d, idx, vals, mask)
        return target + gsum(d)

    # Control-plane masks (repro.core.controlplane): when present, each
    # ToR consults its tables at its *local* slice (t + phase_off) and a
    # ToR whose residual skew exceeds the guard band cannot transmit
    # optically that slice. Versioned tables ("tf_next_v" etc., stacked
    # [V, Tr, N, D, K]) come from reconfigure's staggered-install
    # machinery: each ToR looks up the version its install state selects
    # (j["vsel"]). As with failures, absent inputs fold every branch away
    # and the traced program is exactly the zero-skew, single-version one.
    has_ctrl = "phase_off" in j
    has_vers = "tf_next_v" in j
    Tr = j["tf_next_v"].shape[1] if has_vers else j["tf_next"].shape[0]
    # Telemetry counters (repro.core.telemetry): per-slice per-ToR rows
    # accumulated in the scan carry ("_tin"/"_tdef"/"_tdrop"/"_thwm", reset
    # each slice) and emitted with the per-slice stats. All updates go
    # through upd_add, so sharded runs psum-reconcile them exactly like the
    # occupancy map. telemetry=None folds every counter away: the traced
    # program is exactly the pre-telemetry one.
    has_tele = telemetry is not None
    # Incremental windows (step_slices) pass mask tensors covering only
    # [mask_t0, mask_t0 + window); the traced offset re-bases the absolute
    # slice index for *mask* lookups only. Absent (one-shot runs), indexing
    # stays absolute and the program is unchanged.
    if "mask_t0" in j:
        mt = lambda t: t - j["mask_t0"]
    else:
        mt = lambda t: t
    # population tiers for the per-phase compact views (see module
    # docstring). Sharded, the tier conds are disabled outright: their
    # predicates are shard-local live counts, so shards could pick
    # different branches around the exchange collectives. The local block
    # is already P/num_shards wide, which is what the tiers were for.
    # Batched (vmap over a scenario axis), every data-dependent cond is
    # likewise disabled: a cond with a batched predicate lowers to running
    # *both* branches behind a select, so the phase-skips that pay on a
    # single scenario cost double under vmap — the unconditional program
    # (every skipped branch is a semantic identity) is the faster *and*
    # still bit-identical formulation.
    uncond = axis is not None or batched
    TIERS = [] if uncond else [c for c in (2048, ADMIT_C) if c < P]

    def node_row(name, t):
        """``j[name][t]`` as a full per-node row. Sharded, ``j[name]``
        holds only this shard's owned ToR rows (``[S, ceil(N/D)]``, padded)
        and the full row is gathered once per slice."""
        if axis is None:
            return j[name][mt(t)]
        from ..distributed.collectives import gather_node_row
        with jax.named_scope(tracing.EXCHANGE):
            return gather_node_row(j[name][mt(t)], axis, N)

    caps_all = _build_caps_all(j["conn"], cfg, N)          # [T, NKEY]

    # Failure masks (repro.core.failures): when present, per-slice circuit
    # capacities are recomputed under the mask (a dead link admits nothing,
    # so its packets miss the slice and re-enqueue via the §5.2 machinery;
    # a degraded transceiver admits a fraction), down ToRs stop injecting,
    # and electrical transfers to a down destination are held back. With no
    # masks every branch below folds away and the traced program is exactly
    # the failure-free one (zero-failure bit-identity).
    has_fail = "link_cap" in j

    def caps_at(t, no_t):
        if not has_fail:
            return caps_all[t % T]
        # The masked capacities are recomputed per step rather than
        # precomputed [S, NKEY] like caps_all: reconfigure re-traces this
        # builder every epoch with a different conn, so a full-run
        # precompute would redo all S slices per epoch while each epoch
        # only runs epoch_slices of them. The U scatter-adds here are tiny
        # next to the per-slice packet phases; equivalence with
        # _build_caps_all on healthy masks is pinned by the zero-failure
        # parity tests. Sharded, each shard scatters only its owned
        # link_cap rows (with global row keys) and the partial key maps are
        # psum-exchanged; the electrical row is added once, post-exchange.
        lc = j["link_cap"][mt(t)]              # [N, N] ([rows_local, N] sharded)
        NL = lc.shape[0]
        if axis is None:
            rows = jnp.arange(NL, dtype=jnp.int32)
            own = jnp.ones((NL,), bool)
        else:
            rows = (shard * NL + jnp.arange(NL)).astype(jnp.int32)
            own = rows < N                     # padded rows scatter nothing
            rows = jnp.clip(rows, 0, N - 1)
        caps = jnp.zeros((NKEY,), jnp.int32)
        for k in range(U):
            peer = j["conn"][t % T, rows, k]
            okp = (peer >= 0) & own
            keyk = rows * (N + 1) + jnp.where(peer >= 0, peer, N)
            lck = lc[jnp.arange(NL), jnp.clip(peer, 0, N - 1)]
            # healthy (1.0) and dead (0.0) links stay exact integers; the
            # float product only prices genuinely degraded transceivers
            scaled = jnp.where(
                lck >= 1.0, jnp.int32(cfg.slice_bytes),
                jnp.where(lck <= 0.0, 0,
                          (cfg.slice_bytes * lck).astype(jnp.int32)))
            caps = caps.at[keyk].add(jnp.where(okp, scaled, 0))
        caps = gsum(caps)
        return caps.at[jnp.arange(N) * (N + 1) + N].add(
            jnp.where(no_t, jnp.int32(cfg.elec_bytes), 0))

    # Stacked (injection, transit) tables for the fused first-phase lookup.
    # K is padded to the common max with invalid slots: the valid-slot count
    # (and therefore the hash slot choice) is unchanged. With versioned
    # tables the stack gains a version axis: [2, V, Tr, N, D, K].
    if has_vers:
        K = max(j["inj_next_v"].shape[-1], j["tf_next_v"].shape[-1])
        padk = lambda a, fill: jnp.pad(
            a, [(0, 0)] * 4 + [(0, K - a.shape[-1])], constant_values=fill)
        stk_n = jnp.stack([padk(j["inj_next_v"], -1),
                           padk(j["tf_next_v"], -1)])
        stk_d = jnp.stack([padk(j["inj_dep_v"], 0), padk(j["tf_dep_v"], 0)])
    else:
        K = max(j["inj_next"].shape[-1], j["tf_next"].shape[-1])
        padk = lambda a, fill: jnp.pad(
            a, [(0, 0)] * 3 + [(0, K - a.shape[-1])], constant_values=fill)
        stk_n = jnp.stack([padk(j["inj_next"], -1), padk(j["tf_next"], -1)])
        stk_d = jnp.stack([padk(j["inj_dep"], 0), padk(j["tf_dep"], 0)])

    # per-packet constants bundled into the phase views
    CONSTS = dict(size=j["size"], dst=j["dst"], src=j["src"], flow=j["flow"],
                  seq=j["seq"], is_eleph=j["is_eleph"])
    HOP_FIELDS = ("loc", "nxt", "dep", "relook", "nhops", "t_del")
    if axis is not None:
        # debug ownership trace for the sharding soundness checker: the
        # shard index that capacity-admitted each packet (-1 = never)
        HOP_FIELDS = HOP_FIELDS + ("adm_shard",)
    INJ_FIELDS = ("loc", "nxt", "dep", "relook")

    def mp_hash(t):
        base = pid if per_packet_mp else j["flow"]
        salt = jnp.uint32(t) * jnp.uint32(0x9E3779B9) if per_packet_mp else jnp.uint32(0)
        return _hash32(base.astype(jnp.uint32) + salt)

    def step(state, t):
        with jax.named_scope("fabric"):
            return phases(state, t)

    def phases(state, t):
        s = dict(state)
        if has_tele:
            # per-slice accumulators: zeroed here, filled by the phases
            # below, emitted with the stats at the end of the slice
            s["_tin"] = jnp.zeros((N,), jnp.int32)
            s["_tdef"] = jnp.zeros((N,), jnp.int32)
            s["_tdrop"] = jnp.zeros((N,), jnp.int32)
        h = mp_hash(t)
        # full per-node rows of the (possibly row-sharded) mask tensors,
        # gathered once per slice
        no_t = node_row("node_ok", t) if has_fail else None
        po_t = node_row("phase_off", t) if has_ctrl else None
        sm_t = node_row("skew_miss", t) if has_ctrl else None
        caps = caps_at(t, no_t)

        def vbucket(v, dep_abs):
            return jnp.clip(v["loc"], 0, N - 1) * T2 + dep_abs % T2

        def make_view(s, fields, mask, extras, C):
            """A view of the packet vector: full-width (C None) or the first
            C entries of ``mask`` compacted in index order."""
            if C is None:
                v = {k: s[k] for k in fields}
                v.update(CONSTS)
                v["h"] = h
                v.update(extras)
                return v, None
            with jax.named_scope("compact"):
                idx = _compact_idx(mask, C)
                okc = idx < P
                ic = jnp.clip(idx, 0, P - 1)
                v = {k: s[k][ic] for k in fields}
                v.update({k: a[ic] for k, a in CONSTS.items()})
                v["h"] = h[ic]
                v.update({k: a[ic] & okc for k, a in extras.items()})
                v["_ok"] = okc
            return v, idx

        def write_view(s, v, fields, idx):
            s = dict(s)
            if idx is None:
                s.update((k, v[k]) for k in fields)
                return s
            with jax.named_scope("scatter_back"):
                for k in fields:
                    s[k] = s[k].at[idx].set(v[k], mode="drop")
            return s

        def enqueue_checks(s, v, arrived, off):
            """Congestion detection at enqueue (paper §5.2) against the
            carried occupancy map (which already includes the arrived
            packets): a calendar queue is full if occupancy exceeds the
            admissible amount for its slice. Deferral (+ optional push-back)
            moves the packet's bytes to the next-slice bucket."""
            dep_abs = t + off
            qb = vbucket(v, dep_abs)
            q_occ = s["occ"][qb]
            full = arrived & (off > 0) & (q_occ > limit)
            if not cfg.cc_detect:
                return s, v

            def _defer(op):
                s, v = dict(op[0]), dict(op[1])
                s["occ"] = upd_add(s["occ"], (qb, -v["size"], full),
                                   (vbucket(v, t + 1), v["size"], full))
                if has_tele:
                    s["_tdef"] = upd_add(
                        s["_tdef"],
                        (jnp.clip(v["loc"], 0, N - 1), v["size"], full))
                v["relook"] = v["relook"] | full
                v["dep"] = jnp.where(full, t + 1, v["dep"])
                if cfg.pushback:
                    upd = jnp.where(full, t + T, 0)
                    s["block_until"] = s["block_until"].at[
                        jnp.where(full, v["dst"], 0), dep_abs % T].max(upd)
                return s, v

            if uncond:
                # the deferral's occupancy delta is psum-exchanged inside
                # upd_add, so every shard must enter the branch; an
                # all-false ``full`` makes it the identity
                return _defer((s, v))
            return jax.lax.cond(jnp.any(full), _defer,
                                lambda op: (dict(op[0]), dict(op[1])), (s, v))

        # -- 0. calendar queues activating this slice leave the occupancy map
        with jax.named_scope("activate"):
            act = (s["loc"] >= 0) & (s["dep"] == t)
            if uncond:
                s["occ"] = upd_add(
                    s["occ"],
                    (jnp.clip(s["loc"], 0, N - 1) * T2 + t % T2, -j["size"], act))
            else:
                s["occ"] = jax.lax.cond(
                    jnp.any(act),
                    lambda occ: _scatter_add_masked(
                        occ, jnp.clip(s["loc"], 0, N - 1) * T2 + t % T2,
                        -j["size"], act),
                    lambda occ: occ, s["occ"])

        # -- 1+2. injection & re-lookup of deferred packets (fused lookup) ---
        with jax.named_scope("inject"):
            ready = (j["t_inject"] <= t) & (s["loc"] == NOT_INJECTED)
            if has_fail:
                # a down ToR's hosts cannot inject; the packets simply retry
                # next slice (loc stays NOT_INJECTED)
                ready &= no_t[j["src"]]
            redo = s["relook"] & (s["loc"] >= 0) & (s["dep"] == t)

            def inj_redo_logic(s, v):
                with jax.named_scope("lookup"):
                    if cfg.lookup_impl == "jnp":
                        # one gather serves both phases: injection reads the inj
                        # table at src, deferred packets read the transit table at loc
                        sel = jnp.where(v["ready"], 0, 1)
                        node = jnp.where(v["ready"], v["src"], jnp.clip(v["loc"], 0, N - 1))
                        # a skewed ToR looks its tables up at its *local* slice
                        tl = t + po_t[node] if has_ctrl else t
                        if has_vers:
                            # each ToR reads the table version its install state
                            # selects (old / new / safe) — mixed-version epochs
                            vn = j["vsel"][t - j["vsel_t0"], node]
                            row_n = stk_n[sel, vn, tl % Tr, node, v["dst"]]
                            row_d = stk_d[sel, vn, tl % Tr, node, v["dst"]]
                        else:
                            row_n = stk_n[sel, tl % Tr, node, v["dst"]]
                            row_d = stk_d[sel, tl % Tr, node, v["dst"]]
                        nxt_i, off_i = _select_slot(row_n, row_d, v["h"])
                        nxt_r, off_r = nxt_i, off_i
                    else:
                        nxt_i, off_i = _lookup(j["inj_next"], j["inj_dep"], t,
                                               v["src"], v["dst"], v["h"], cfg.lookup_impl)
                        nxt_r, off_r = _lookup(j["tf_next"], j["tf_dep"], t,
                                               jnp.clip(v["loc"], 0, N - 1), v["dst"],
                                               v["h"], cfg.lookup_impl)
                    if cfg.flow_pausing:
                        # elephants wait for the direct circuit their *source ToR*
                        # believes is coming (its local clock)
                        tsrc = t + po_t[v["src"]] if has_ctrl else t
                        fd = j["first_direct"][tsrc % T, v["src"], v["dst"]]
                        use_direct = v["is_eleph"] & (fd >= 0)
                        nxt_i = jnp.where(use_direct, v["dst"], nxt_i)
                        off_i = jnp.where(use_direct, fd, off_i)
                if cfg.pushback:
                    # hosts hold traffic whose *target* slice bucket was pushed back
                    blocked = s["block_until"][v["dst"], (t + off_i) % T] > t
                else:
                    blocked = jnp.zeros(v["ready"].shape, bool)
                inject = v["ready"] & ~blocked
                if has_tele:
                    s["_tin"] = upd_add(
                        s["_tin"],
                        (jnp.clip(v["src"], 0, N - 1), v["size"], inject))
                v["loc"] = jnp.where(inject, v["src"], v["loc"])
                v["nxt"] = jnp.where(inject, nxt_i, v["nxt"])
                v["dep"] = jnp.where(inject, t + off_i, v["dep"])
                with jax.named_scope("enqueue"):
                    s["occ"] = upd_add(s["occ"], (vbucket(v, t + off_i), v["size"],
                                                  inject & (off_i > 0)))
                    s, v = enqueue_checks(s, v, inject, jnp.where(inject, off_i, 0))
                n_blocked = jnp.sum(v["ready"] & blocked)
                # deferred packets re-enter the pipeline with a fresh action
                v["nxt"] = jnp.where(v["redo"], nxt_r, v["nxt"])
                v["dep"] = jnp.where(v["redo"], t + off_r, v["dep"])
                v["relook"] = v["relook"] & ~v["redo"]
                with jax.named_scope("enqueue"):
                    s["occ"] = upd_add(s["occ"], (vbucket(v, t + off_r), v["size"],
                                                  v["redo"] & (off_r > 0)))
                return s, v, n_blocked

            inj_mask = ready | redo
            inj_cnt = jnp.sum(inj_mask)

            def inj_full(s):
                v, idx = make_view(s, INJ_FIELDS, None, dict(ready=ready, redo=redo), None)
                s, v, n_blocked = inj_redo_logic(dict(s), v)
                return write_view(s, v, INJ_FIELDS, idx), n_blocked

            def inj_compact(C):
                def fn(s, C=C):
                    v, idx = make_view(s, INJ_FIELDS, inj_mask,
                                       dict(ready=ready, redo=redo), C)
                    s, v, n_blocked = inj_redo_logic(dict(s), v)
                    return write_view(s, v, INJ_FIELDS, idx), n_blocked
                return fn

            if uncond:
                # unconditional: the injection exchange collectives must run on
                # every shard even when this shard has nothing to inject
                s, n_blocked = inj_full(s)
                n_blocked = gsum(n_blocked)
            else:
                inj_fn = inj_full
                for c in TIERS[::-1]:
                    inj_fn = (lambda s, cc=c, inner=inj_fn:
                              jax.lax.cond(inj_cnt <= cc, inj_compact(cc), inner, s))
                s, n_blocked = jax.lax.cond(
                    inj_cnt > 0, inj_fn,
                    lambda s: (dict(s), jnp.zeros((), jnp.int32)), s)

        def on_switch_bytes(occ):
            """Per-node switch-resident bytes: occupancy columns within the
            offload horizon (all columns without offloading)."""
            occ2 = occ.reshape(N, T2)
            if not cfg.offload:
                return occ2.sum(axis=1)
            hor = max(0, min(cfg.offload_horizon, T2 - 1))
            cols = (t + 1 + jnp.arange(hor)) % T2
            return occ2[:, cols].sum(axis=1)

        # -- 3. transmission with cut-through chaining ---------------------
        used = jnp.zeros((NKEY,), jnp.int32)
        buf_now = on_switch_bytes(s["occ"])
        if has_tele:
            s["_thwm"] = buf_now    # slice-local high-water, maxed per hop

        def hop_logic(s, v, used, buf_now, backlog_min, rx_backlog_min,
                      resc_min):
            want = v["active"]
            if has_fail:
                # the electrical fabric cannot terminate at a down ToR;
                # dead optical circuits are already capacity-zero
                want &= ~((v["nxt"] == N) & ~no_t[v["dst"]])
            if has_ctrl:
                # a ToR whose residual skew exceeds the guard band misses
                # its optical transmit windows this slice (§7); the
                # asynchronous electrical fabric is exempt. The packet
                # misses its slice and re-enqueues via the §5.2 machinery.
                want &= ~(sm_t[jnp.clip(v["loc"], 0, N - 1)] &
                          (v["nxt"] < N))
            with jax.named_scope("admit"):
                if cfg.pushback:
                    # push-back rejects at the *sender*: no transmission into a
                    # full downstream switch (paper §5.2); rejected packets miss
                    # the slice and defer instead of being dropped on arrival.
                    # FIFO admission against the receiver's remaining buffer room.
                    need_buf = want & (v["nxt"] < N) & (v["nxt"] != v["dst"])
                    room = jnp.maximum(cfg.switch_buffer - buf_now, 0)
                    adm_rx, _ = _admit(jnp.clip(v["nxt"], 0, N - 1), v["size"],
                                       need_buf, room, N, impl=cfg.admit_impl,
                                       axis=axis, num_shards=num_shards)
                    # rx rejections are monotone within the slice: the rx cut is
                    # a FIFO prefix per receiver, a receiver's room only shrinks
                    # (buf_now only receives arrivals), and a candidate's rx
                    # prefix can drop only by bytes of earlier same-receiver
                    # packets that transmitted — each of which arrived at that
                    # receiver, shrinking room by at least as much. The first
                    # rx-rejected index per receiver therefore poisons its whole
                    # suffix for the rest of the slice.
                    rej_rx = need_buf & ~adm_rx
                    rx_backlog_min = rx_backlog_min.at[
                        jnp.where(rej_rx, jnp.clip(v["nxt"], 0, N - 1), 0)].min(
                        jnp.where(rej_rx, v["gidx"], PG))
                    want &= adm_rx | ~need_buf
                key = jnp.clip(v["loc"], 0, N - 1) * (N + 1) + jnp.clip(v["nxt"], 0, N)
                admitted, consumed = _admit(key, v["size"], want, caps - used,
                                            NKEY, impl=cfg.admit_impl,
                                            axis=axis, num_shards=num_shards)
                used = used + consumed
                if "adm_shard" in v:
                    # ownership trace: only the shard whose block holds the
                    # packet ever admits it (its peers hold no copy), which the
                    # toolkit sharding checker asserts
                    v["adm_shard"] = jnp.where(admitted, shard, v["adm_shard"])
                # Rejected packets form the slice's backlog: admission is a
                # cumulative-prefix cut per group and capacities only shrink, so a
                # packet positioned after a rejected one in its group can never be
                # admitted later this slice. Remember the minimum rejected index
                # per group; later hops drop those provably-rejected candidates.
                if not cfg.pushback:
                    # only *wanted* rejections poison the suffix: packets cut
                    # from want by failure/skew masks never consumed capacity
                    # and must not filter their healthy group-mates
                    rejected = want & ~admitted
                    backlog_min = backlog_min.at[jnp.where(rejected, key, 0)].min(
                        jnp.where(rejected, v["gidx"], PG))
                else:
                    # Under push-back the only bytes that can ever *leave* a
                    # candidate's capacity prefix belong to an earlier
                    # same-group member that was rx-admitted but
                    # capacity-rejected this slice: it stays a candidate and
                    # may flip to rx-rejected at a later hop (capacity-admitted
                    # members transmitted — their bytes became consumed
                    # capacity and never come back; rx-rejected members were
                    # never in the prefix). Track the first such "rescuable"
                    # index per group; an rx-exempt candidate (electrical, or
                    # delivering directly to its destination) rejected with no
                    # rescuable predecessor is then provably rejected for the
                    # rest of the slice. rx-subject rejections are never
                    # marked: their bytes participate in other candidates' rx
                    # prefixes, and cutting them would perturb the rx cut.
                    resc = need_buf & adm_rx & ~admitted
                    resc_min = resc_min.at[jnp.where(resc, key, 0)].min(
                        jnp.where(resc, v["gidx"], PG))
                    # the markable test reads resc_min across *all* packets of
                    # the group, so the per-shard partial mins are exchanged
                    # before the read
                    resc_min = gmin(resc_min)
                    markable = want & ~admitted & ~need_buf & \
                        (v["gidx"] < resc_min[key])
                    backlog_min = backlog_min.at[jnp.where(markable, key, 0)].min(
                        jnp.where(markable, v["gidx"], PG))
            is_elec = admitted & (v["nxt"] == N)
            moved = admitted & ~is_elec
            newloc = jnp.where(moved, v["nxt"], v["loc"])
            at_dst = (moved & (v["nxt"] == v["dst"])) | is_elec
            # electrical fabric delivers with one-slice transit delay
            v["t_del"] = jnp.where(at_dst, jnp.where(is_elec, t + 1, t),
                                   v["t_del"])

            # reorder accounting (deliveries are capacity-bounded per hop, so
            # the compact path is the common case even for a full-width view)
            with jax.named_scope("reorder"):
                Pv = v["loc"].shape[0]

                def _re_small(ms):
                    max_seq, reorder = ms
                    i2 = _compact_idx(at_dst, SMALL_C)
                    ok2 = i2 < Pv
                    ci = jnp.clip(i2, 0, Pv - 1)
                    fl = jnp.where(ok2, v["flow"][ci], 0)
                    sq = jnp.where(ok2, v["seq"][ci], -1)
                    prev = max_seq[fl]
                    reorder = reorder + jnp.sum(ok2 & (sq < prev))
                    return max_seq.at[fl].max(jnp.where(ok2, sq, -1)), reorder

                def _re_full(ms):
                    max_seq, reorder = ms
                    prev = max_seq[v["flow"]]
                    reorder = reorder + jnp.sum(at_dst & (v["seq"] < prev))
                    return max_seq.at[jnp.where(at_dst, v["flow"], 0)].max(
                        jnp.where(at_dst, v["seq"], -1)), reorder

                if Pv <= SMALL_C:
                    s["max_seq"], s["reorder"] = _re_full((s["max_seq"], s["reorder"]))
                else:
                    s["max_seq"], s["reorder"] = jax.lax.cond(
                        jnp.sum(at_dst) <= SMALL_C, _re_small, _re_full,
                        (s["max_seq"], s["reorder"]))
                # max_seq is replicated high-water state: exchange before the
                # next hop's reads. reorder stays a per-shard partial count
                # (each shard saw only its own deliveries against the *global*
                # max_seq) and is summed once at the end of the run.
                s["max_seq"] = gmax(s["max_seq"])

            v["loc"] = jnp.where(at_dst, DELIVERED, newloc)
            v["nhops"] = v["nhops"] + admitted.astype(jnp.int32)
            # transit lookup at the new node (its local slice, its version)
            in_transit = moved & ~at_dst
            with jax.named_scope("lookup"):
                node_t = jnp.clip(v["loc"], 0, N - 1)
                tl = t + po_t[node_t] if has_ctrl else t
                if has_vers:
                    vn = j["vsel"][t - j["vsel_t0"], node_t]
                    rn = j["tf_next_v"][vn, tl % Tr, node_t, v["dst"]]
                    rd = j["tf_dep_v"][vn, tl % Tr, node_t, v["dst"]]
                    nxt_t, off_t = _select_slot(rn, rd, v["h"])
                else:
                    nxt_t, off_t = _lookup(j["tf_next"], j["tf_dep"], tl,
                                           node_t, v["dst"], v["h"],
                                           cfg.lookup_impl)
                v["nxt"] = jnp.where(in_transit, nxt_t, v["nxt"])
                v["dep"] = jnp.where(in_transit, t + off_t, v["dep"])
            with jax.named_scope("enqueue"):
                # buffer-overflow drops on arrival at a new switch; a rejection
                # also pushes the sender back (paper §5.2)
                buf_now = upd_add(buf_now, (jnp.clip(v["loc"], 0, N - 1),
                                            v["size"], in_transit))
                if has_tele:
                    s["_thwm"] = jnp.maximum(s["_thwm"], buf_now)
                overflow = in_transit & \
                    (buf_now[jnp.clip(v["loc"], 0, N - 1)] > cfg.switch_buffer)
                if cfg.pushback:
                    upd = jnp.where(overflow, t + T, 0)
                    s["block_until"] = s["block_until"].at[
                        jnp.where(overflow, v["dst"], 0), v["dep"] % T].max(upd)
                if has_tele:
                    # count dropped bytes at the switch the packet overflowed,
                    # before loc is overwritten with the DROPPED sentinel
                    s["_tdrop"] = upd_add(
                        s["_tdrop"],
                        (jnp.clip(v["loc"], 0, N - 1), v["size"], overflow))
                v["loc"] = jnp.where(overflow, DROPPED, v["loc"])
                arrived = in_transit & ~overflow
                s["occ"] = upd_add(s["occ"], (vbucket(v, t + off_t), v["size"],
                                              arrived & (off_t > 0)))
                s, v = enqueue_checks(s, v, arrived, jnp.where(in_transit, off_t, 0))
            # the backlog cuts are read by every shard at the next hop's
            # want0 filter: exchange the per-shard partial minima
            backlog_min = gmin(backlog_min)
            rx_backlog_min = gmin(rx_backlog_min)
            return s, v, used, buf_now, backlog_min, rx_backlog_min, resc_min

        backlog_min = jnp.full((NKEY,), PG, jnp.int32)
        rx_backlog_min = jnp.full((N,), PG, jnp.int32)
        resc_min = jnp.full((NKEY,), PG, jnp.int32)
        def backlog_cut(want0, s, backlog_min, rx_backlog_min):
            with jax.named_scope("gather"):
                key_all = jnp.clip(s["loc"], 0, N - 1) * (N + 1) + \
                    jnp.clip(s["nxt"], 0, N)
                if not cfg.pushback:
                    return want0 & (pid < backlog_min[key_all])
                # push-back-aware backlog filter: drop candidates at-or-after
                # a receiver's first rx-rejected index (rx rejection is
                # monotone — see hop_logic), and rx-exempt candidates
                # strictly *after* their group's first marked capacity
                # rejection (the marked packet itself stays in the sort as
                # the byte anchor of every successor's over-capacity
                # prefix). rx-subject capacity rejections stay unfiltered:
                # their prefixes can lose bytes to later rx flips, and
                # their bytes feed other candidates' rx prefixes.
                rx_subject = (s["nxt"] >= 0) & (s["nxt"] < N) & \
                    (s["nxt"] != j["dst"])
                want0 &= ~(rx_subject &
                           (pid >= rx_backlog_min[jnp.clip(s["nxt"], 0, N - 1)]))
                return want0 & ~(~rx_subject & (pid > backlog_min[key_all]))

        for _hop in range(cfg.hops_per_slice):
            with jax.named_scope("hop"):
                with jax.named_scope("backlog_filter"):
                    want0 = (s["loc"] >= 0) & (s["dep"] == t) & (s["nxt"] >= 0) & \
                            (s["nhops"] < cfg.max_hops)
                    if _hop > 0:
                        # every cut starts the slice at PG, where the filter
                        # is the identity (hop 0 reads none); gather them only
                        # once some group or receiver holds one. The cuts are
                        # exchanged at the end of every hop, so all shards
                        # take the same branch.
                        engaged = jnp.any(backlog_min < PG)
                        if cfg.pushback:
                            engaged |= jnp.any(rx_backlog_min < PG)
                        want0 = jax.lax.cond(
                            engaged, backlog_cut, lambda w, *_: w, want0,
                            {k: s[k] for k in ("loc", "nxt")}, backlog_min,
                            rx_backlog_min)
                cnt0 = jnp.sum(want0)

                def hop_full(carry, want0=want0):
                    s, used, buf_now, backlog_min, rx_backlog_min, resc_min = carry
                    v, idx = make_view(s, HOP_FIELDS, None,
                                       dict(active=want0), None)
                    v["gidx"] = pid
                    (s, v, used, buf_now, backlog_min, rx_backlog_min,
                     resc_min) = hop_logic(dict(s), v, used, buf_now, backlog_min,
                                           rx_backlog_min, resc_min)
                    return (write_view(s, v, HOP_FIELDS, idx), used, buf_now,
                            backlog_min, rx_backlog_min, resc_min)

                def hop_compact(C, want0=want0):
                    def fn(carry, C=C, want0=want0):
                        (s, used, buf_now, backlog_min, rx_backlog_min,
                         resc_min) = carry
                        v, idx = make_view(s, HOP_FIELDS, want0, {}, C)
                        v["active"] = v.pop("_ok")
                        v["gidx"] = jnp.minimum(idx, P).astype(jnp.int32)
                        (s, v, used, buf_now, backlog_min, rx_backlog_min,
                         resc_min) = hop_logic(dict(s), v, used, buf_now,
                                               backlog_min, rx_backlog_min,
                                               resc_min)
                        return (write_view(s, v, HOP_FIELDS, idx), used, buf_now,
                                backlog_min, rx_backlog_min, resc_min)
                    return fn

                if uncond:
                    # every shard runs every hop: the admission exchange and
                    # aggregate reconciliation are collective
                    s, used, buf_now, backlog_min, rx_backlog_min, resc_min = \
                        hop_full((s, used, buf_now, backlog_min, rx_backlog_min,
                                  resc_min))
                else:
                    hop_fn = hop_full
                    for c in TIERS[::-1]:
                        hop_fn = (lambda carry, cc=c, inner=hop_fn:
                                  jax.lax.cond(cnt0 <= cc, hop_compact(cc), inner,
                                               carry))
                    s, used, buf_now, backlog_min, rx_backlog_min, resc_min = \
                        jax.lax.cond(
                            cnt0 == 0, lambda c: (dict(c[0]),) + c[1:], hop_fn,
                            (s, used, buf_now, backlog_min, rx_backlog_min,
                             resc_min))

        # -- 4. handle packets that missed their slice ----------------------
        with jax.named_scope("missed"):
            missed = (s["loc"] >= 0) & (s["dep"] == t)
            miss_cnt = jnp.sum(missed)

            def missed_body(s):
                s = dict(s)
                bump = t + 1 if cfg.cc_detect else t + T  # paused a cycle (§5.2)
                if cfg.cc_detect:
                    s["relook"] = s["relook"] | missed
                s["occ"] = upd_add(
                    s["occ"], (jnp.clip(s["loc"], 0, N - 1) * T2 + bump % T2,
                               j["size"], missed))
                if has_tele:
                    s["_tdef"] = upd_add(
                        s["_tdef"],
                        (jnp.clip(s["loc"], 0, N - 1), j["size"], missed))
                s["dep"] = jnp.where(missed, bump, s["dep"])
                if cfg.pushback:
                    upd = jnp.where(missed, t + T, 0)
                    s["block_until"] = s["block_until"].at[j["dst"], t % T].max(upd)
                return s

            if uncond:
                s = missed_body(s)       # occ delta is psum-exchanged inside
                miss_cnt = gsum(miss_cnt)
            else:
                s = jax.lax.cond(miss_cnt > 0, missed_body, lambda s: dict(s), s)
            if axis is not None and cfg.pushback:
                # block_until collected per-shard partial maxima all step
                # (defer, overflow, missed sites); it is only read at the next
                # slice's injection, so one exchange here keeps it replicated
                s["block_until"] = gmax(s["block_until"])

        # -- 5. per-slice stats (column sums of the occupancy map) ----------
        with jax.named_scope("stats"):
            on_sw = on_switch_bytes(s["occ"])
            if cfg.offload:
                off_sw = s["occ"].reshape(N, T2).sum(axis=1) - on_sw
            else:
                off_sw = jnp.zeros_like(on_sw)
            stats = dict(
                delivered_bytes=gsum(
                    jnp.sum(jnp.where(s["t_del"] == t, j["size"], 0))),
                dropped=gsum(jnp.sum(s["loc"] == DROPPED)),
                buf_bytes=on_sw, offl_bytes=off_sw,
                blocked_inj=n_blocked, slice_miss=miss_cnt,
            )
            if has_tele:
                # circuit utilization: optical bytes moved vs granted, per
                # source switch (the electrical egress column N is excluded).
                # tele_delivered / tele_lat_hist are NOT accumulated here:
                # delivery is terminal (t_del is written once), so both are
                # reconstructed from the terminal packet state with one P-wide
                # scatter per run (_tele_delivery_rows) instead of a
                # full-population pass every slice.
                stats.update(
                    tele_injected=s["_tin"],
                    tele_deferred=s["_tdef"], tele_dropped=s["_tdrop"],
                    tele_qhwm=jnp.maximum(s["_thwm"], on_sw),
                    tele_util_used=used.reshape(N, N + 1)[:, :N].sum(axis=1),
                    tele_util_cap=caps.reshape(N, N + 1)[:, :N].sum(axis=1),
                )
        return s, stats

    return step


def _tele_delivery_rows(final, j, telemetry, num_slices: int, t0=0,
                        axis=None):
    """Per-slice delivered rows [S, N] + latency histogram [S, B] from the
    terminal packet state. Delivery is terminal — ``t_del`` is written
    exactly once — so one scatter over the population here is bit-identical
    to accumulating ``t_del == t`` rows inside the scan, at 1/S the cost.
    ``t0`` re-bases window runs (:func:`step_slices`); deliveries outside
    [t0, t0 + num_slices) belong to other windows (or never landed) and
    scatter nothing. Sharded, each shard scatters its packet block and the
    rows are psum-reconciled to match the replicated in-scan counters."""
    N = j["conn"].shape[1]
    rel = final["t_del"] - t0
    ok = (rel >= 0) & (rel < num_slices)
    relc = jnp.clip(rel, 0, max(num_slices - 1, 0))
    rows = jnp.zeros((num_slices, N), jnp.int32).at[
        relc, jnp.clip(j["dst"], 0, N - 1)].add(jnp.where(ok, j["size"], 0))
    # bucket i counts latencies in (edges[i-1], edges[i]]; last is overflow
    edges = jnp.asarray(telemetry.lat_edges, jnp.int32)
    lat = jnp.maximum(final["t_del"] - j["t_inject"], 0)
    bucket = jnp.searchsorted(edges, lat, side="left").astype(jnp.int32)
    hist = jnp.zeros((num_slices, telemetry.num_buckets), jnp.int32).at[
        relc, bucket].add(jnp.where(ok, 1, 0))
    if axis is not None:
        with jax.named_scope(tracing.EXCHANGE):
            rows = jax.lax.psum(rows, axis)
            hist = jax.lax.psum(hist, axis)
    return rows, hist


def _sim_out(final, ys, j=None, telemetry=None, num_slices=None, axis=None):
    """Assemble the result dict from the scan's final state + stacked
    per-slice stats (shared by the single-device, sharded, and vmapped
    entry points). In-scan telemetry rows pass through when present; the
    delivery-derived rows are reconstructed post-scan."""
    out = dict(
        t_deliver=final["t_del"], loc_final=final["loc"], nhops=final["nhops"],
        delivered_bytes=ys["delivered_bytes"], dropped=ys["dropped"],
        buf_bytes=ys["buf_bytes"], offl_bytes=ys["offl_bytes"],
        blocked_inj=ys["blocked_inj"], slice_miss=ys["slice_miss"],
        reorder_cnt=final["reorder"],
    )
    for k in TELE_KEYS:
        if k in ys:
            out[k] = ys[k]
    if telemetry is not None:
        with jax.named_scope("fabric/finish"):
            rows, hist = _tele_delivery_rows(final, j, telemetry, num_slices,
                                             axis=axis)
        out["tele_delivered"] = rows
        out["tele_lat_hist"] = hist
    return out


def _sim_body(j, cfg: FabricConfig, num_slices: int, per_packet_mp: bool,
              num_flows: int, batched: bool = False, telemetry=None):
    step = _make_step(j, cfg, per_packet_mp, num_flows, batched=batched,
                      telemetry=telemetry)
    final, ys = jax.lax.scan(step, _init_state(j, num_flows, telemetry),
                             jnp.arange(num_slices, dtype=jnp.int32))
    return _sim_out(final, ys, j, telemetry, num_slices)


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4, 5))
def _simulate_jit(j, cfg: FabricConfig, num_slices: int, per_packet_mp: bool,
                  num_flows: int, telemetry: TelemetryConfig | None = None):
    with tracing.retrace("_simulate_jit"):
        return _sim_body(j, cfg, num_slices, per_packet_mp, num_flows,
                         telemetry=telemetry)


# ---------------------------------------------------------------------------
# sharded + vmapped entry points (ISSUE 7)
# ---------------------------------------------------------------------------

# j keys partitioned over the "tor" mesh axis: per-packet arrays by
# contiguous global-index block, per-slice node tensors by owned ToR rows.
# Everything else (schedule, tables, replicated aggregates) is replicated.
_PACKET_KEYS = ("src", "dst", "size", "t_inject", "flow", "seq", "is_eleph")
_NODE_ROW_KEYS = ("link_cap", "node_ok", "phase_off", "skew_miss")
# per-packet outputs come back as per-shard blocks, concatenated in shard
# order == global index order
_PACKET_OUT = ("t_deliver", "loc_final", "nhops", "adm_shard")


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4, 5, 6, 7))
def _simulate_sharded_jit(j, cfg: FabricConfig, num_slices: int,
                          per_packet_mp: bool, num_flows: int,
                          num_shards: int, mesh,
                          telemetry: TelemetryConfig | None = None):
    from jax.sharding import PartitionSpec as PS

    def body(jl):
        step = _make_step(jl, cfg, per_packet_mp, num_flows,
                          axis="tor", num_shards=num_shards,
                          telemetry=telemetry)
        st0 = _init_state(jl, num_flows, telemetry)
        st0["adm_shard"] = jnp.full_like(st0["loc"], -1)
        final, ys = jax.lax.scan(step, st0,
                                 jnp.arange(num_slices, dtype=jnp.int32))
        out = _sim_out(final, ys, jl, telemetry, num_slices, axis="tor")
        # reorder was carried as a per-shard partial count (see _make_step)
        with jax.named_scope("fabric/finish"), \
                jax.named_scope(tracing.EXCHANGE):
            out["reorder_cnt"] = jax.lax.psum(out["reorder_cnt"], "tor")
        out["adm_shard"] = final["adm_shard"]
        return out

    def in_spec(k, a):
        if k in _PACKET_KEYS:
            return PS("tor")
        if k in _NODE_ROW_KEYS:
            return PS(*([None, "tor"] + [None] * (a.ndim - 2)))
        return PS(*([None] * a.ndim))

    in_specs = {k: in_spec(k, a) for k, a in j.items()}
    out_specs = dict(
        t_deliver=PS("tor"), loc_final=PS("tor"), nhops=PS("tor"),
        adm_shard=PS("tor"), delivered_bytes=PS(), dropped=PS(),
        buf_bytes=PS(), offl_bytes=PS(), blocked_inj=PS(), slice_miss=PS(),
        reorder_cnt=PS(),
    )
    if telemetry is not None:
        # counter rows are psum-reconciled inside the step -> replicated
        out_specs.update({k: PS() for k in TELE_KEYS})
    with tracing.retrace("_simulate_sharded_jit"):
        return jax.shard_map(body, mesh=mesh, in_specs=(in_specs,),
                             out_specs=out_specs, check_vma=False)(j)


def _check_impls(cfg: FabricConfig):
    if cfg.lookup_impl not in ("jnp", "pallas", "pallas-interpret"):
        raise ValueError(f"unknown lookup_impl {cfg.lookup_impl!r}: expected "
                         "'jnp', 'pallas', or 'pallas-interpret'")
    if cfg.admit_impl not in ("xla", "pallas", "pallas-interpret"):
        raise ValueError(f"unknown admit_impl {cfg.admit_impl!r}: expected "
                         "'xla', 'pallas', or 'pallas-interpret'")


def simulate_sharded(tables: FabricTables, wl: Workload, cfg: FabricConfig,
                     num_slices: int, num_shards: int | None = None,
                     failures=None, control=None,
                     telemetry: TelemetryConfig | None = None,
                     with_debug: bool = False):
    """Run :func:`simulate` sharded over a 1-D device mesh — bit-identical
    to the single-device path (asserted by the multi-device differential
    suite, ``tests/test_fabric_sharded.py``).

    The packet vector is partitioned in contiguous global-index blocks
    (padded with never-injecting packets when the population does not
    divide), the dense failure/control mask tensors are partitioned by
    owned ToR rows (each device holds only ``ceil(N / D)`` rows of
    ``link_cap[S, N, N]``), and the per-ToR aggregates stay replicated with
    every update exchanged through
    :mod:`repro.distributed.collectives`. Admission/lookup run local to the
    owning shard; cross-shard arrivals are exchanged per slice as static-
    shape per-key aggregates (see :func:`_admit`).

    Args:
        num_shards: devices to shard over (default: all visible). Any
            count 1..len(devices) works, including counts that do not
            divide the ToR or packet counts.
        with_debug: also return a debug dict (``adm_shard`` — the shard
            that admitted each packet, ``owner`` — the shard owning each
            packet's block, ``num_shards``, ``packet_block``) for the
            :func:`repro.core.toolkit.check_sharding` soundness checker.
    """
    _check_impls(cfg)
    from ..distributed import sharding as dshard
    mesh, D = dshard.fabric_mesh(num_shards)
    T, N, U = tables.conn.shape
    P = wl.num_packets
    Pl = dshard.block_len(P, D)
    pp = lambda a, fill, dt: jnp.asarray(
        dshard.pad_packet_axis(np.asarray(a, dt), D, fill))
    dev = lambda a, dt=jnp.int32: jnp.asarray(a, dt)
    j = dict(
        conn=dev(tables.conn), tf_next=dev(tables.tf_next),
        tf_dep=dev(tables.tf_dep), inj_next=dev(tables.inj_next),
        inj_dep=dev(tables.inj_dep), first_direct=dev(tables.first_direct),
        src=pp(wl.src, 0, np.int32), dst=pp(wl.dst, 0, np.int32),
        size=pp(wl.size, 0, np.int32),
        # pad packets "inject" after the run ends: they never act
        t_inject=pp(wl.t_inject, num_slices, np.int32),
        flow=pp(wl.flow, 0, np.int32), seq=pp(wl.seq, 0, np.int32),
        is_eleph=pp(wl.is_eleph, False, bool),
    )
    if failures is not None:
        failures.validate(num_slices, N)
        j["link_cap"] = dev(dshard.pad_node_rows(
            np.asarray(failures.link_cap, np.float32), D, 1.0), jnp.float32)
        j["node_ok"] = dev(dshard.pad_node_rows(
            np.asarray(failures.node_ok, bool), D, True), jnp.bool_)
    if control is not None:
        if cfg.lookup_impl != "jnp":
            raise ValueError(
                "control-plane masks need lookup_impl='jnp': per-ToR local "
                f"slices make lookups per-packet in time (got "
                f"{cfg.lookup_impl!r})")
        control.validate(num_slices, N)
        j["phase_off"] = dev(dshard.pad_node_rows(
            np.asarray(control.phase_off, np.int32), D, 0))
        j["skew_miss"] = dev(dshard.pad_node_rows(
            np.asarray(control.skew_miss, bool), D, False), jnp.bool_)
    num_flows = int(max(wl.flow.max() + 1, 1)) if P else 1
    out = _simulate_sharded_jit(j, cfg, num_slices,
                                tables.multipath == "packet", num_flows,
                                D, mesh, telemetry)
    out = {k: np.asarray(v) for k, v in out.items()}
    adm_shard = out.pop("adm_shard")[:P]
    for k in _PACKET_OUT:
        if k in out:
            out[k] = out[k][:P]      # drop the block padding
    tele = counters_from_out(out, telemetry)
    res = SimResult(**out, telemetry=tele)
    if with_debug:
        return res, dict(adm_shard=adm_shard,
                         owner=dshard.shard_owner(np.arange(P), P, D),
                         num_shards=D, packet_block=Pl)
    return res


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4, 5))
def _simulate_fleet_jit(jb, cfg: FabricConfig, num_slices: int,
                        per_packet_mp: bool, num_flows: int,
                        telemetry: TelemetryConfig | None = None):
    with tracing.retrace("_simulate_fleet_jit"):
        return jax.vmap(
            lambda jj: _sim_body(jj, cfg, num_slices, per_packet_mp,
                                 num_flows, batched=True, telemetry=telemetry)
        )(jb)


def simulate_fleet(tables, wls, cfg: FabricConfig, num_slices: int,
                   failures=None, control=None,
                   telemetry: TelemetryConfig | None = None
                   ) -> list[SimResult]:
    """Run a whole scenario sweep as **one** batched XLA program:
    :func:`simulate` vmapped over a scenario axis — bit-identical to the
    per-scenario Python loop, without per-scenario dispatch overhead. The
    body is built with the data-dependent phase-skip conds disabled
    (``batched=True``): under vmap a cond runs both branches behind a
    select, so the unconditional program (every skipped branch is a
    semantic identity) is both faster and exactly equal.

    Args:
        tables: one :class:`FabricTables` shared by every scenario, or a
            list (one per scenario) whose tables all share shapes and
            multipath mode — e.g. the same scheme compiled over different
            schedules, or schemes with shared table shapes.
        wls: list of :class:`Workload`, all with the same packet count
            (seed sweeps naturally satisfy this; ``num_flows`` is the max
            across scenarios — extra rows of a scenario's ``max_seq`` are
            simply never touched).
        failures / control: ``None``, or a list of per-scenario masks
            (``None`` entries are not allowed — presence is a static
            branch, so it must agree across the batch; pass
            ``FailureMasks.healthy(...)`` / ``ControlMasks.perfect(...)``
            to mix faulty and clean scenarios).

    Returns one :class:`SimResult` per scenario, in order.
    """
    _check_impls(cfg)
    B = len(wls)
    if B == 0:
        return []
    tabs = list(tables) if isinstance(tables, (list, tuple)) else [tables] * B
    if len(tabs) != B:
        raise ValueError(f"{len(tabs)} tables for {B} workloads")
    if any(t.multipath != tabs[0].multipath for t in tabs):
        raise ValueError("fleet tables must share a multipath mode (it is a "
                         "static branch)")
    shapes = {w.num_packets for w in wls}
    if len(shapes) != 1:
        raise ValueError(f"fleet workloads must share a packet count, got "
                         f"{sorted(shapes)}")
    T, N, U = tabs[0].conn.shape
    stk = lambda arrs, dt: jnp.asarray(np.stack([np.asarray(a) for a in arrs]),
                                       dt)
    jb = dict(
        conn=stk([t.conn for t in tabs], jnp.int32),
        tf_next=stk([t.tf_next for t in tabs], jnp.int32),
        tf_dep=stk([t.tf_dep for t in tabs], jnp.int32),
        inj_next=stk([t.inj_next for t in tabs], jnp.int32),
        inj_dep=stk([t.inj_dep for t in tabs], jnp.int32),
        first_direct=stk([t.first_direct for t in tabs], jnp.int32),
        src=stk([w.src for w in wls], jnp.int32),
        dst=stk([w.dst for w in wls], jnp.int32),
        size=stk([w.size for w in wls], jnp.int32),
        t_inject=stk([w.t_inject for w in wls], jnp.int32),
        flow=stk([w.flow for w in wls], jnp.int32),
        seq=stk([w.seq for w in wls], jnp.int32),
        is_eleph=stk([w.is_eleph for w in wls], jnp.bool_),
    )
    if failures is not None:
        if len(failures) != B or any(f is None for f in failures):
            raise ValueError(
                "failures must be one mask set per scenario (mask presence "
                "is a static branch; use FailureMasks.healthy for clean "
                "scenarios)")
        for f in failures:
            f.validate(num_slices, N)
        jb["link_cap"] = stk([f.link_cap for f in failures], jnp.float32)
        jb["node_ok"] = stk([f.node_ok for f in failures], jnp.bool_)
    if control is not None:
        if cfg.lookup_impl != "jnp":
            raise ValueError(
                "control-plane masks need lookup_impl='jnp': per-ToR local "
                f"slices make lookups per-packet in time (got "
                f"{cfg.lookup_impl!r})")
        if len(control) != B or any(c is None for c in control):
            raise ValueError(
                "control must be one mask set per scenario (mask presence "
                "is a static branch; use ControlMasks.perfect for clean "
                "scenarios)")
        for c in control:
            c.validate(num_slices, N)
        jb["phase_off"] = stk([c.phase_off for c in control], jnp.int32)
        jb["skew_miss"] = stk([c.skew_miss for c in control], jnp.bool_)
    num_flows = max(max(int(w.flow.max()) + 1 if w.num_packets else 1, 1)
                    for w in wls)
    out = _simulate_fleet_jit(jb, cfg, num_slices,
                              tabs[0].multipath == "packet", num_flows,
                              telemetry)
    out = {k: np.asarray(v) for k, v in out.items()}
    teles = [counters_from_out(out, telemetry, index=i) for i in range(B)]
    for k in TELE_KEYS:
        out.pop(k, None)
    return [SimResult(**{k: v[i] for k, v in out.items()}, telemetry=teles[i])
            for i in range(B)]


# ---------------------------------------------------------------------------
# incremental simulation (ISSUE 8): init_state / ingest / step_slices /
# finalize — the one-shot scan split open so fabric state carries across
# calls, which is what lets OpenOpticsNet run as a long-lived clocked
# service (repro.core.net).
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class FabricState:
    """Live fabric state between :func:`step_slices` calls.

    ``j`` holds the deployed tables + the packet population so far (device
    arrays, *without* mask tensors — those are window-scoped and joined per
    :func:`step_slices` call); ``state`` is the scan carry exactly as
    :func:`_make_step` leaves it (per-packet sentinels, calendar-queue
    occupancy, push-back map, reorder tracking, telemetry accumulators).
    ``clock`` is the absolute slice index the next window starts at;
    ``chunks`` collects each window's stacked per-slice stats (host side,
    concatenated by :func:`finalize`).
    """

    j: dict
    state: dict
    cfg: FabricConfig
    telemetry: "TelemetryConfig | None"
    per_packet_mp: bool
    num_flows: int
    clock: int = 0
    chunks: list = dataclasses.field(default_factory=list)

    @property
    def num_nodes(self) -> int:
        return int(self.j["conn"].shape[1])

    @property
    def num_packets(self) -> int:
        return int(self.j["src"].shape[0])


@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6, 7))
def _window_jit(j, state, t0, cfg: FabricConfig, n_slices: int,
                per_packet_mp: bool, num_flows: int,
                telemetry: TelemetryConfig | None = None):
    with tracing.retrace("_window_jit"):
        step = _make_step(j, cfg, per_packet_mp, num_flows,
                          telemetry=telemetry)
        final, ys = jax.lax.scan(step, state,
                                 t0 + jnp.arange(n_slices, dtype=jnp.int32))
        if telemetry is not None:
            # window-local delivery rows from the terminal state: deliveries
            # from earlier windows fall outside [t0, t0 + n) and scatter
            # nothing
            with jax.named_scope("fabric/finish"):
                rows, hist = _tele_delivery_rows(final, j, telemetry,
                                                 n_slices, t0)
            ys = dict(ys, tele_delivered=rows, tele_lat_hist=hist)
        return final, ys


def init_state(tables: FabricTables, wl: Workload | None, cfg: FabricConfig,
               telemetry: TelemetryConfig | None = None) -> FabricState:
    """Open an incremental run: deployed tables + an initial packet
    population (``None`` for an empty fabric — :func:`ingest` adds traffic
    later). The same static knobs as :func:`simulate` apply."""
    _check_impls(cfg)
    dev = lambda a, dt=jnp.int32: jnp.asarray(a, dt)
    j = dict(
        conn=dev(tables.conn), tf_next=dev(tables.tf_next),
        tf_dep=dev(tables.tf_dep), inj_next=dev(tables.inj_next),
        inj_dep=dev(tables.inj_dep), first_direct=dev(tables.first_direct),
    )
    if wl is None:
        z = np.zeros((0,), np.int32)
        j.update(src=dev(z), dst=dev(z), size=dev(z), t_inject=dev(z),
                 flow=dev(z), seq=dev(z), is_eleph=dev(z, jnp.bool_))
        num_flows = 1
    else:
        j.update(src=dev(wl.src), dst=dev(wl.dst), size=dev(wl.size),
                 t_inject=dev(wl.t_inject), flow=dev(wl.flow),
                 seq=dev(wl.seq), is_eleph=dev(wl.is_eleph, jnp.bool_))
        num_flows = int(max(wl.flow.max() + 1, 1)) if wl.num_packets else 1
    return FabricState(j=j, state=_init_state(j, num_flows, telemetry),
                       cfg=cfg, telemetry=telemetry,
                       per_packet_mp=tables.multipath == "packet",
                       num_flows=num_flows)


def ingest(fs: FabricState, wl: Workload) -> FabricState:
    """Join new packets to a live run. ``wl.t_inject`` is absolute fabric
    time (inject slices already elapsed never fire — the caller shifts;
    :meth:`repro.core.net.OpenOpticsNet.ingest` shifts by its clock).
    Flow ids are absolute too: reusing an id continues that flow's
    in-order sequence tracking. Growing the population re-traces the
    window program (packet count is a static shape)."""
    P = wl.num_packets
    if P == 0:
        return fs
    dev = lambda a, dt=jnp.int32: jnp.asarray(a, dt)
    cat = lambda a, b: jnp.concatenate([a, b])
    fs.j.update(
        src=cat(fs.j["src"], dev(wl.src)),
        dst=cat(fs.j["dst"], dev(wl.dst)),
        size=cat(fs.j["size"], dev(wl.size)),
        t_inject=cat(fs.j["t_inject"], dev(wl.t_inject)),
        flow=cat(fs.j["flow"], dev(wl.flow)),
        seq=cat(fs.j["seq"], dev(wl.seq)),
        is_eleph=cat(fs.j["is_eleph"], dev(wl.is_eleph, jnp.bool_)),
    )
    s = fs.state
    full = lambda fill, dt=jnp.int32: jnp.full((P,), fill, dt)
    s.update(
        loc=cat(s["loc"], full(NOT_INJECTED)),
        nxt=cat(s["nxt"], full(-1)),
        dep=cat(s["dep"], full(0)),
        relook=cat(s["relook"], full(False, jnp.bool_)),
        nhops=cat(s["nhops"], full(0)),
        t_del=cat(s["t_del"], full(-1)),
    )
    nf = int(max(wl.flow.max() + 1, 1))
    if nf > fs.num_flows:
        s["max_seq"] = jnp.concatenate(
            [s["max_seq"], jnp.full((nf - fs.num_flows,), -1, jnp.int32)])
        fs.num_flows = nf
    return fs


def step_slices(fs: FabricState, num_slices: int, failures=None,
                control=None) -> FabricState:
    """Advance the fabric ``num_slices`` slices (one jitted window scan).

    ``failures`` / ``control`` masks cover **this window only**
    (``[num_slices, N]``-shaped rows, row 0 = the current clock slice);
    their presence is a static branch per window, exactly as in
    :func:`simulate`. The carry state picks up where the last window left
    off, so a run split across any window boundaries is bit-identical to
    the one-shot scan (asserted by ``tests/test_telemetry.py``)."""
    N = fs.num_nodes
    jw = dict(fs.j)
    if failures is not None:
        failures.validate(num_slices, N)
        jw["link_cap"] = jnp.asarray(failures.link_cap, jnp.float32)
        jw["node_ok"] = jnp.asarray(failures.node_ok, jnp.bool_)
    if control is not None:
        if fs.cfg.lookup_impl != "jnp":
            raise ValueError(
                "control-plane masks need lookup_impl='jnp': per-ToR local "
                f"slices make lookups per-packet in time (got "
                f"{fs.cfg.lookup_impl!r})")
        control.validate(num_slices, N)
        jw["phase_off"] = jnp.asarray(control.phase_off, jnp.int32)
        jw["skew_miss"] = jnp.asarray(control.skew_miss, jnp.bool_)
    if failures is not None or control is not None:
        # window-local mask rows: _make_step re-bases mask lookups only
        jw["mask_t0"] = jnp.int32(fs.clock)
    with tracing.span("advance.dispatch"):
        fs.state, ys = _window_jit(jw, fs.state, jnp.int32(fs.clock), fs.cfg,
                                   int(num_slices), fs.per_packet_mp,
                                   fs.num_flows, fs.telemetry)
    with tracing.span("advance.device_wait"):
        ys = jax.block_until_ready(ys)
    with tracing.span("advance.stats_copy"):
        fs.chunks.append({k: np.asarray(v) for k, v in ys.items()})
    fs.clock += int(num_slices)
    return fs


def finalize(fs: FabricState) -> SimResult:
    """Close the run: assemble the same :class:`SimResult` the one-shot
    :func:`simulate` would return for the windows run so far (the state
    stays live — finalize may be called repeatedly as a checkpoint)."""
    N = fs.num_nodes
    stat_keys = ("delivered_bytes", "dropped", "buf_bytes", "offl_bytes",
                 "blocked_inj", "slice_miss")
    tele_keys = TELE_KEYS if fs.telemetry is not None else ()
    if fs.chunks:
        ys = {k: np.concatenate([c[k] for c in fs.chunks])
              for k in stat_keys + tele_keys}
    else:
        B = fs.telemetry.num_buckets if fs.telemetry is not None else 0
        empt = {"delivered_bytes": (0,), "dropped": (0,),
                "buf_bytes": (0, N), "offl_bytes": (0, N),
                "blocked_inj": (0,), "slice_miss": (0,),
                "tele_injected": (0, N), "tele_delivered": (0, N),
                "tele_deferred": (0, N), "tele_dropped": (0, N),
                "tele_qhwm": (0, N), "tele_util_used": (0, N),
                "tele_util_cap": (0, N), "tele_lat_hist": (0, B)}
        ys = {k: np.zeros(empt[k], np.int32) for k in stat_keys + tele_keys}
    out = dict(
        t_deliver=np.asarray(fs.state["t_del"]),
        loc_final=np.asarray(fs.state["loc"]),
        nhops=np.asarray(fs.state["nhops"]),
        reorder_cnt=np.asarray(fs.state["reorder"]),
        **{k: ys[k] for k in stat_keys + tele_keys},
    )
    tele = counters_from_out(out, fs.telemetry)
    return SimResult(**out, telemetry=tele)


def simulate_incremental(tables: FabricTables, wl: Workload, cfg: FabricConfig,
                         num_slices: int, window: int | None = None,
                         failures=None, control=None,
                         telemetry: TelemetryConfig | None = None) -> SimResult:
    """:func:`simulate`, replayed through the incremental API in windows of
    ``window`` slices (default: one window). Field-for-field identical to
    the one-shot run — counters included; full-run masks are sliced per
    window."""
    fs = init_state(tables, wl, cfg, telemetry)
    window = num_slices if window is None else int(window)
    if window <= 0:
        raise ValueError(f"window must be positive, got {window}")
    while fs.clock < num_slices:
        n = min(window, num_slices - fs.clock)
        t0, t1 = fs.clock, fs.clock + n
        fw = cw = None
        if failures is not None:
            failures.validate(num_slices, len(tables.conn[0]))
            fw = dataclasses.replace(
                failures, link_cap=failures.link_cap[t0:t1],
                node_ok=failures.node_ok[t0:t1])
        if control is not None:
            control.validate(num_slices, len(tables.conn[0]))
            cw = dataclasses.replace(
                control, skew_ns=control.skew_ns[t0:t1],
                phase_off=control.phase_off[t0:t1],
                skew_miss=control.skew_miss[t0:t1],
                ctrl_delay=control.ctrl_delay[t0:t1],
                ctrl_ok=control.ctrl_ok[t0:t1])
        step_slices(fs, n, failures=fw, control=cw)
    return finalize(fs)
