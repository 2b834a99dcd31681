"""Reference of the ``ucmp`` routing scheme."""
from __future__ import annotations

import numpy as np

INF = np.int64(1 << 40)


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


tables = ucmp
