"""Reference of the ``vlb`` routing scheme."""
from __future__ import annotations

import numpy as np

from bench.reference.tables_ref import direct, has_circuit


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


tables = vlb
