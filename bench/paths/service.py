"""Path ``service``: the clocked ``OpenOpticsNet`` service. One call is an
episode: a fresh net with telemetry on, one ``ingest(wl)``, then
``steps`` service steps of ``advance(window_slices)`` plus ``snapshot()``,
then ``service_result()``. A service step is timed on the caller's side;
``service_step_ms_p98`` is the 98th percentile over every step of the
window. The two steps of an episode that inject its packets are by far
its slowest (on a TPU v5e 0.60 s and 0.44 s, the others 0.02-0.08 s).
They are 2 of its 40 steps, so the 95th percentile falls on the edge
between them and the rest, and one slow step elsewhere moves it across;
the 98th lies among the first steps, with ten or more steps beyond it.

The check compares each checked episode's ``service_result`` with the
reference, bit for bit; its telemetry counters with the host replay of
the reference's terminal state; and every snapshot's packet and byte
totals with what the reference says at that clock."""
from __future__ import annotations

import time

import numpy as np

from bench.paths._common import result_arrays


class Driver:
    def __init__(self, h):
        self.h = h
        self.window = int(h.mix["window_slices"])
        self.steps = int(h.mix["steps"])
        self.slices_per_call = self.window * self.steps
        if self.slices_per_call != h.num_slices:
            raise ValueError("window_slices x steps must equal num_slices")
        self.step_s: list[float] = []
        self.episode(0, self.steps_warmup)
        self.step_s.clear()

    # a window program of window_slices and the snapshot's host copies are
    # every shape an episode uses, so two steps warm them all
    steps_warmup = 2

    def episode(self, i: int, steps: int) -> dict:
        h = self.h
        with h.spans("deploy"):
            net = h.deploy(telemetry={})
        with h.spans("ingest"):
            net.ingest(h.program_workload(i))
        snaps = []
        for _ in range(steps):
            t0 = time.perf_counter()
            with h.spans("advance"):
                net.advance(self.window)
            with h.spans("snapshot"):
                snap = net.snapshot()
            self.step_s.append(time.perf_counter() - t0)
            snaps.append(dict(clock=snap["clock"], packets=snap["packets"],
                              bytes=snap["bytes"]))
        with h.spans("service_result"):
            res = net.service_result()
        tele = res.telemetry
        counters = {f: np.asarray(getattr(tele, f)) for f in (
            "injected_bytes", "delivered_bytes", "deferred_bytes",
            "dropped_bytes", "queue_hwm", "util_used", "util_cap",
            "lat_hist")}
        counters["lat_edges"] = tuple(tele.lat_edges)
        return dict(result=result_arrays(res), telemetry=counters,
                    snapshots=snaps)

    def call(self, i: int) -> dict:
        return self.episode(i, self.steps)

    def end_to_end(self) -> dict:
        ms = 1e3 * np.asarray(self.step_s)
        return {"service_step_ms_p98": float(np.percentile(ms, 98))}

    def close(self):
        pass


def prepare(h):
    return Driver(h)


def control_outputs(ctl: dict, wl: dict, mix: dict) -> dict:
    """A reference result in this path's output form: the result, and the
    snapshots it implies at every step's clock (for the control)."""
    from bench.reference import telemetry_ref
    snaps = []
    for c in range(mix["window_slices"], mix["num_slices"] + 1,
                   mix["window_slices"]):
        tot = telemetry_ref.snapshot_totals(ctl, wl, c)
        b = dict(tot["bytes"])
        b["in_flight"] = b.pop("in_flight+dropped")
        snaps.append(dict(clock=c, packets=tot["packets"], bytes=b))
    return {"result": ctl, "snapshots": snaps}
