"""Host replays of the service layer: the telemetry counters (copied from
the program's ``toolkit.check_telemetry``, so that the yardstick cannot
change with the program) and the packet and byte totals a ``snapshot()``
reports, both worked out from the reference's terminal state.

Each function returns a list of human-readable violations; empty = sound.
"""
from __future__ import annotations

import numpy as np

from .fabric_ref import DELIVERED, DROPPED, NOT_INJECTED

COUNTERS = ("injected_bytes", "delivered_bytes", "deferred_bytes",
            "dropped_bytes", "queue_hwm", "util_used", "util_cap")


def check_counters(tele: dict, ref: dict, wl: dict, num_slices: int) -> list[str]:
    """The program's counters ``tele`` (``COUNTERS`` as ``[S, N]`` arrays,
    ``lat_hist`` ``[S, B]``, ``lat_edges``) against the reference result
    ``ref`` on workload ``wl``: shapes and signs, per-slice delivered rows,
    utilisation within its grant, high-water marks over end-of-slice
    residency, and the exact replay of delivered rows, the latency
    histogram, injected and dropped totals and per-source conservation."""
    bad: list[str] = []
    S = int(num_slices)
    N = np.asarray(ref["buf_bytes"]).shape[1]
    edges = np.asarray(tele["lat_edges"])
    B = edges.size + 1
    for f in COUNTERS:
        a = np.asarray(tele[f])
        if a.shape != (S, N):
            bad.append(f"telemetry.{f} shaped {a.shape}, expected ({S}, {N})")
        elif (a < 0).any():
            t, n = [int(x[0]) for x in np.nonzero(a < 0)]
            bad.append(f"telemetry.{f}[{t}, {n}] = {a[t, n]} negative")
    hist = np.asarray(tele["lat_hist"])
    if hist.shape != (S, B):
        bad.append(f"telemetry.lat_hist shaped {hist.shape}, "
                   f"expected ({S}, {B})")
    if bad:
        return bad

    dlv = np.asarray(tele["delivered_bytes"])
    rows = dlv.sum(axis=1)
    want_rows = np.asarray(ref["delivered_bytes"])
    for t in np.nonzero(rows != want_rows)[0][:8]:
        bad.append(f"slice {t}: delivered_bytes row sums to {rows[t]}, "
                   f"the reference delivers {want_rows[t]}")
    used, cap = np.asarray(tele["util_used"]), np.asarray(tele["util_cap"])
    for t, n in zip(*[x[:8] for x in np.nonzero(used > cap)]):
        bad.append(f"slice {t} ToR {n}: util_used {used[t, n]} > granted "
                   f"{cap[t, n]}")
    hwm, buf = np.asarray(tele["queue_hwm"]), np.asarray(ref["buf_bytes"])
    for t, n in zip(*[x[:8] for x in np.nonzero(hwm < buf)]):
        bad.append(f"slice {t} switch {n}: queue_hwm {hwm[t, n]} below "
                   f"end-of-slice residency {buf[t, n]}")

    src, dst = np.asarray(wl["src"]), np.asarray(wl["dst"])
    size = np.asarray(wl["size"]).astype(np.int64)
    t_inj = np.asarray(wl["t_inject"])
    loc, t_del = np.asarray(ref["loc_final"]), np.asarray(ref["t_deliver"])
    in_run = (t_del >= 0) & (t_del < S)
    want_dlv = np.zeros((S, N), np.int64)
    np.add.at(want_dlv, (t_del[in_run], dst[in_run]), size[in_run])
    for t, d in zip(*[x[:8] for x in np.nonzero(want_dlv != dlv)]):
        bad.append(f"slice {t} dst {d}: delivered_bytes {dlv[t, d]}, "
                   f"replay says {want_dlv[t, d]}")
    lat = np.maximum(t_del[in_run] - t_inj[in_run], 0)
    bidx = np.searchsorted(edges, lat, side="left")
    want_hist = np.zeros((S, B), np.int64)
    np.add.at(want_hist, (t_del[in_run], bidx), 1)
    for t, b in zip(*[x[:8] for x in np.nonzero(want_hist != hist)]):
        bad.append(f"slice {t} bucket {b}: lat_hist {hist[t, b]}, replay "
                   f"says {want_hist[t, b]}")
    injected = loc != NOT_INJECTED
    dropped = loc == DROPPED
    flight = injected & ~dropped & ~(in_run & (loc == DELIVERED))
    inj_tot = np.asarray(tele["injected_bytes"]).sum(axis=0, dtype=np.int64)
    want_inj = np.bincount(src[injected], weights=size[injected],
                           minlength=N).astype(np.int64)
    for n in np.nonzero(inj_tot != want_inj)[0][:8]:
        bad.append(f"ToR {n}: injected_bytes total {inj_tot[n]}, replay "
                   f"says {want_inj[n]}")
    got_drop = int(np.asarray(tele["dropped_bytes"]).sum())
    want_drop = int(size[dropped].sum())
    if got_drop != want_drop:
        bad.append(f"dropped_bytes total {got_drop}, dropped packets carry "
                   f"{want_drop} bytes")
    per_src = np.zeros((3, N), np.int64)
    for i, m in enumerate((in_run & (loc == DELIVERED), dropped, flight)):
        per_src[i] = np.bincount(src[m], weights=size[m], minlength=N)
    gap = want_inj - per_src.sum(axis=0)
    for n in np.nonzero(gap)[0][:8]:
        bad.append(f"ToR {n}: conservation gap {gap[n]} bytes")
    return bad


def snapshot_totals(ref: dict, wl: dict, clock: int) -> dict:
    """What ``snapshot()`` at ``clock`` must report, from the reference's
    terminal state: packets pending (not yet due: with push-back off a
    packet injects in its own slice), delivered before the clock, dropped
    by the end of slice ``clock - 1``, and the rest in flight; bytes for
    the same groups, with dropped and in-flight bytes summed (the terminal
    state does not say when a packet dropped)."""
    size = np.asarray(wl["size"]).astype(np.int64)
    t_del = np.asarray(ref["t_deliver"])
    pending = np.asarray(wl["t_inject"]) >= clock
    delivered = (t_del >= 0) & (t_del < clock)
    n_drop = int(np.asarray(ref["dropped"])[clock - 1]) if clock else 0
    P = size.size
    packets = dict(total=P, pending=int(pending.sum()),
                   delivered=int(delivered.sum()), dropped=n_drop)
    packets["in_flight"] = P - packets["pending"] - packets["delivered"] - n_drop
    total = int(size.sum())
    byts = dict(total=total, pending=int(size[pending].sum()),
                delivered=int(size[delivered].sum()))
    byts["in_flight+dropped"] = total - byts["pending"] - byts["delivered"]
    return dict(packets=packets, bytes=byts)


def check_snapshot(snap: dict, ref: dict, wl: dict) -> list[str]:
    """One snapshot frame against :func:`snapshot_totals` at its clock."""
    want = snapshot_totals(ref, wl, int(snap["clock"]))
    got_b = dict(snap["bytes"])
    got_b["in_flight+dropped"] = got_b.get("in_flight", 0) + got_b.get(
        "dropped", 0)
    got = dict(packets=snap["packets"], bytes=got_b)
    return [f"clock {snap['clock']}: {kind}.{k} = {got[kind].get(k)}, "
            f"replay says {v}"
            for kind in ("packets", "bytes") for k, v in want[kind].items()
            if got[kind].get(k) != v]
