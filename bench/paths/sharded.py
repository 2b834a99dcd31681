"""Path ``sharded``: ``simulate_sharded(tables, wl, cfg, S,
num_shards=chips)`` back to back over the cell's chips, with the tables
the deployment compiled. The check compares each checked call's result
with the single-device reference, bit for bit."""
from __future__ import annotations

from bench.paths._common import result_arrays


class Driver:
    def __init__(self, h):
        from repro.core import FabricTables
        self.h = h
        with h.spans("deploy"):
            net = h.deploy()
        self.tables = FabricTables.build(net.schedule, net.routing)
        self.cfg = net.fabric_cfg
        self.slices_per_call = h.num_slices
        self.call(0)

    def call(self, i: int) -> dict:
        from repro.core import simulate_sharded
        wl = self.h.program_workload(i)
        with self.h.spans("simulate_sharded"):
            res = simulate_sharded(self.tables, wl, self.cfg,
                                   self.slices_per_call,
                                   num_shards=self.h.chips)
        return dict(result=result_arrays(res))

    def end_to_end(self) -> dict:
        return {}

    def close(self):
        self.tables = None


def prepare(h):
    return Driver(h)
