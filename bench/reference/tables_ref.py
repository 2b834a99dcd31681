"""Plain reference of the control plane the benchmark's deployments use:
the RotorNet round-robin schedule, direct-circuit routing, VLB and UCMP
time-flow tables, written from their definitions and independent of the
program's vectorised compilers. The benchmark compares the tables the
program deployed with these, entry for entry, and runs the reference
fabric on these.

Table layout (the fabric's): ``[T, N, D, K]`` per (arrival slice mod T,
node, destination, multipath slot); ``*_next`` is the egress peer (-1 for
an empty slot), ``*_dep`` the departure-slice offset. Valid slots are
contiguous from slot 0.
"""
from __future__ import annotations

import numpy as np

INF = np.int64(1 << 40)


def rotor_schedule(n_nodes: int, n_uplinks: int) -> np.ndarray:
    """RotorNet (Mellette et al., SIGCOMM'17): uplink k of node i in slice t
    connects to ``(i + 1 + (t + k*T//U) mod T) mod N``, ``T = N - 1``; every
    pair gets a direct circuit once per cycle on each uplink."""
    T = n_nodes - 1
    conn = np.empty((T, n_nodes, n_uplinks), np.int32)
    for k in range(n_uplinks):
        phase = (k * T) // n_uplinks
        for t in range(T):
            conn[t, :, k] = (np.arange(n_nodes) + 1 + (t + phase) % T) % n_nodes
    return conn


def has_circuit(conn: np.ndarray) -> np.ndarray:
    """has[t, n, d]: some uplink of n connects to d in slice t."""
    T, N, U = conn.shape
    has = np.zeros((T, N, N), bool)
    for k in range(U):
        for t in range(T):
            has[t, np.arange(N), conn[t, :, k]] = True
    return has


def first_direct(conn: np.ndarray) -> np.ndarray:
    """first[t, n, d]: slices to wait at n from slice t until a circuit
    n -> d is up (the search runs to the end of the next cycle); -1 if the
    schedule never has one."""
    has = has_circuit(conn)
    T = has.shape[0]
    first = np.full(has.shape, -1, np.int32)
    for t in range(T):
        for w in range(2 * T - 1 - t, -1, -1):   # latest first, so the
            first[t] = np.where(has[(t + w) % T], w, first[t])   # least wins
    return first


def direct(conn: np.ndarray):
    """Hold each packet until the direct circuit to its destination."""
    fd = first_direct(conn)
    N = conn.shape[1]
    nxt = np.where(fd >= 0, np.arange(N, dtype=np.int32), -1)[..., None]
    dep = np.where(fd >= 0, fd, 0).astype(np.int32)[..., None]
    return nxt.astype(np.int32), dep


def vlb(conn: np.ndarray, kpaths: int) -> dict:
    """Valiant load balancing: at injection, straight to the destination if
    a circuit to it is up now, else sprayed over the current peers (uplink
    order, duplicates kept, at most ``kpaths``), leaving this slice; transit
    nodes wait for the direct circuit."""
    T, N, U = conn.shape
    has = has_circuit(conn)
    n_i, d_i = np.meshgrid(np.arange(N), np.arange(N), indexing="ij")
    inj_next = np.full((T, N, N, kpaths), -1, np.int32)
    for t in range(T):
        spray = (n_i != d_i) & ~has[t]
        rank = np.zeros((N, N), np.int64)
        for k in range(U):
            peer = conn[t, :, k][:, None]
            use = spray & (peer != d_i)
            put = use & (rank < kpaths)
            inj_next[t, n_i[put], d_i[put], rank[put]] = np.broadcast_to(
                peer, (N, N))[put]
            rank += use
        now = (n_i != d_i) & has[t]
        inj_next[t, n_i[now], d_i[now], 0] = d_i[now]
    tf_next, tf_dep = direct(conn)
    return dict(tf_next=tf_next, tf_dep=tf_dep, inj_next=inj_next,
                inj_dep=np.zeros_like(inj_next), multipath="packet")


def ucmp(conn: np.ndarray, max_hop: int = 4, kpaths: int = 4) -> dict:
    """Uniform-cost multipath: every departure option whose (arrival slice,
    hop count) equals the best achievable, in (departure slice, uplink)
    order, up to ``kpaths`` options.

    The cost is a backward DP over two schedule cycles of the time-expanded
    graph, one circuit hop per slice, waiting free:
    ``cost[t, n] = min(cost[t+1, n], 1 + t*B if peer == d, 1 + cost[t+1,
    peer])`` with ``cost[t, d] = t*B`` and ``B`` large enough that arrival
    slice dominates hops. From a start slice t the options are the hops at
    slices tt >= t, while the cost of waiting until tt is still the best,
    that attain the best cost; a peer repeated on a later uplink of the same
    slice counts once. Injection and transit tables are the same."""
    T, N, U = conn.shape
    H = 2 * T
    B = np.int64((max_hop + H) * (H + 2) + 1)
    diag = np.arange(N)
    cost = np.full((H + 1, N, N), INF, np.int64)          # [t, n, d]
    cost[H, diag, diag] = H * B
    for t in range(H - 1, -1, -1):
        c = cost[t + 1].copy()
        for k in range(U):
            peer = conn[t % T, :, k]
            via = np.where(peer[:, None] == diag[None, :], t * B,
                           cost[t + 1][peer])
            c = np.minimum(c, via + 1)
        c[diag, diag] = t * B
        cost[t] = c
    nxt = np.full((T, N, N, kpaths), -1, np.int32)
    dep = np.zeros((T, N, N, kpaths), np.int32)
    for t in range(T):
        best = cost[t]
        filled = np.zeros((N, N), np.int64)
        waiting = np.ones((N, N), bool)
        for tt in range(t, H):
            waiting &= cost[tt] == best
            if not waiting.any():
                break
            seen = []
            for k in range(U):
                peer = conn[tt % T, :, k]
                via = np.where(peer[:, None] == diag[None, :], tt * B,
                               cost[tt + 1][peer])
                hit = waiting & (via + 1 == best) & (filled < kpaths)
                for p in seen:
                    hit &= (peer != p)[:, None]
                seen.append(peer)
                n_i, d_i = np.nonzero(hit)
                nxt[t, n_i, d_i, filled[n_i, d_i]] = peer[n_i]
                dep[t, n_i, d_i, filled[n_i, d_i]] = tt - t
                filled[n_i, d_i] += 1
    return dict(tf_next=nxt, tf_dep=dep, inj_next=nxt.copy(),
                inj_dep=dep.copy(), multipath="packet")


SCHEMES = {"vlb": vlb, "ucmp": ucmp}


def deployment_tables(deployment: dict) -> dict:
    """Every table of a deployment file's schedule and routing scheme."""
    if deployment["schedule"] != "round_robin":
        raise ValueError(f"no reference for schedule {deployment['schedule']}")
    conn = rotor_schedule(deployment["tors"], deployment["uplinks"])
    routing = dict(deployment["routing"])
    scheme = SCHEMES[routing.pop("scheme")]
    tables = scheme(conn, **routing)
    tables.update(conn=conn, first_direct=first_direct(conn))
    return tables
