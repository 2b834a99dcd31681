"""Path ``run``: the one-shot entry point, ``OpenOpticsNet.run(wl, S)``,
called back to back on the deployment, each call ending in its result on
the host. The check compares each checked call's result with the
reference, field by field and bit for bit."""
from __future__ import annotations

from bench.paths._common import result_arrays


class Driver:
    def __init__(self, h):
        self.h = h
        with h.spans("deploy"):
            self.net = h.deploy()
        self.slices_per_call = h.num_slices
        self.call(0)                      # warm-up: compiles or loads

    def call(self, i: int) -> dict:
        wl = self.h.program_workload(i)
        with self.h.spans("run"):
            res = self.net.run(wl, self.slices_per_call)
        return dict(result=result_arrays(res))

    def end_to_end(self) -> dict:
        return {}

    def close(self):
        self.net = None


def prepare(h):
    return Driver(h)
