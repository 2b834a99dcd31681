"""Reference of the ``round_robin`` schedule (one dimension): RotorNet with
one uplink, Opera's rotors offset in phase with several."""
from __future__ import annotations

import numpy as np


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


conn = rotor_schedule
