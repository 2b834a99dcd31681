"""Bring-up smoke test: the paper-scale optical fabric on a TPU, through the
user API.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # simulate_sharded over four chips

The deployment is the paper's RotorNet: 108 ToRs with one uplink each, a
round-robin schedule of 10 us slices at 100 Gbps (125,000 bytes per circuit
per slice), congestion detection on, and the synthetic KV-store trace at 40%
load, capped at 200,000 packets. The fabric runs 400 slices, so two-hop
paths drain over more than three 107-slice cycles. Phases on one chip:

* ``vlb`` / ``ucmp``: ``OpenOpticsNet.deploy_topo`` / ``deploy_routing`` /
  ``run`` with the default backends, once cold and once warm. Each result
  must equal, bit for bit, the same run on the CPU backend of this process
  (the data plane is all integer). The CPU runs go in a worker thread while
  the chip works.
* ``vlb-pallas`` / ``ucmp-pallas``: the same runs with
  ``lookup_impl="pallas", admit_impl="pallas"``; they must equal the
  default-backend runs on the chip bit for bit.
* ``service``: telemetry on, ``ingest`` + four ``advance`` windows +
  ``snapshot`` on the VLB deployment. ``toolkit.check_telemetry`` must find
  no violation, and the service result must equal the one-shot run.

With ``--four-chips`` the only phase is ``simulate_sharded(num_shards=4)``
on the VLB deployment, against single-device ``simulate`` bit for bit, with
``toolkit.check_sharding`` clean and every chip of the mesh holding data.

Each phase prints its device, compile seconds (backend compiles that JAX
reported during the phase), cold and warm wall seconds, and what it
checked. The last line of standard output is a JSON object with ``"ok":
true`` and the device; it is printed only when every check passed. Without
a TPU the script exits non-zero before running anything.
"""
from __future__ import annotations

import argparse
import concurrent.futures
import dataclasses
import json
import os
import pathlib
import sys
import threading
import time

import numpy as np

REPO = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(REPO / "src"))

import jax  # noqa: E402

from repro.core import (FabricConfig, FabricTables, OpenOpticsNet,  # noqa: E402
                        round_robin, simulate, simulate_sharded, synthesize,
                        toolkit, ucmp, vlb)
from repro.distributed.sharding import fabric_mesh  # noqa: E402

PAPER_TORS = 108
SLICE_US = 10.0
SLICE_BYTES = 125_000     # 100 Gbps x 10 us, per circuit per slice
DEMAND_SLICES = 64        # arrival window of the trace generator
NUM_SLICES = 400          # > 3 schedule cycles of 107 slices
SERVICE_WINDOWS = 4
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class CompileClock:
    """Sums the backend compile seconds JAX reports in the thread that made
    the clock (a monitoring listener; the CPU reference thread's compiles
    do not count); ``lap()`` returns the seconds since the previous lap."""

    def __init__(self):
        self.total = self._mark = 0.0
        self._thread = threading.get_ident()

    def __call__(self, event, duration, **_):
        if event == COMPILE_EVENT and threading.get_ident() == self._thread:
            self.total += duration

    def lap(self) -> float:
        lap, self._mark = self.total - self._mark, self.total
        return lap


@dataclasses.dataclass
class Deployment:
    n_tors: int
    sched: object
    routings: dict
    wl: object

    def net(self, scheme: str, telemetry=None, **fabric) -> OpenOpticsNet:
        cfg = dict(node="rack", node_num=self.n_tors, uplink=1,
                   slice_us=SLICE_US, fabric=fabric_config(**fabric))
        if telemetry is not None:
            cfg["telemetry"] = telemetry
        net = OpenOpticsNet(cfg)
        if not net.deploy_topo(self.sched):
            raise RuntimeError("round-robin schedule failed deploy_topo")
        net.deploy_routing(self.routings[scheme])
        return net


def fabric_config(**fabric) -> dict:
    return dict(slice_bytes=SLICE_BYTES, cc_detect=True, **fabric)


def rotor_deployment(n_tors: int = PAPER_TORS,
                     max_packets: int = 200_000) -> Deployment:
    """RotorNet at ``n_tors`` ToRs with VLB (one slot) and UCMP tables, and
    the KV-store workload (the generator's default 200,000-packet cap)."""
    sched = round_robin(n_tors, 1, slice_us=SLICE_US)
    wl = synthesize("kvstore", n_tors, DEMAND_SLICES, slice_bytes=SLICE_BYTES,
                    load=0.4, max_packets=max_packets, seed=0)
    return Deployment(n_tors, sched,
                      {"vlb": vlb(sched, kpaths=1), "ucmp": ucmp(sched)}, wl)


def mismatches(a, b) -> list[str]:
    """SimResult fields (telemetry aside) that differ in shape or value."""
    return [f.name for f in dataclasses.fields(a) if f.name != "telemetry"
            and not np.array_equal(getattr(a, f.name), getattr(b, f.name))]


def _summary(res) -> str:
    done = int((res.t_deliver >= 0).sum())
    return (f"delivered {done}/{res.t_deliver.size} packets, "
            f"{int(res.delivered_bytes.sum())} bytes, "
            f"dropped {int(res.dropped[-1])}")


def one_shot_phase(dep: Deployment, scheme: str, num_slices: int,
                   **fabric):
    """A cold and a warm ``net.run`` of a fresh deployment; returns
    (result, cold s, warm s, problems)."""
    net = dep.net(scheme, **fabric)
    t0 = time.perf_counter()
    first = net.run(dep.wl, num_slices)
    t1 = time.perf_counter()
    again = net.run(dep.wl, num_slices)
    t2 = time.perf_counter()
    bad = mismatches(first, again)
    problems = [f"warm repeat differs in {bad}"] if bad else []
    if not (first.t_deliver >= 0).any():
        problems.append("no packet was delivered")
    return first, t1 - t0, t2 - t1, problems


def cpu_reference(dep: Deployment, scheme: str, num_slices: int):
    """The default-backend run on the CPU backend of this process."""
    with jax.default_device(jax.devices("cpu")[0]):
        return dep.net(scheme).run(dep.wl, num_slices)


def service_phase(dep: Deployment, num_slices: int, windows: int,
                  reference):
    """Telemetry-on service: ingest, ``windows`` advance windows, snapshot.
    Returns (first window s, later windows s, problems)."""
    net = dep.net("vlb", telemetry={})
    net.ingest(dep.wl)
    per = num_slices // windows
    t0 = time.perf_counter()
    net.advance(per)
    t1 = time.perf_counter()
    for _ in range(windows - 1):
        net.advance(per)
    t2 = time.perf_counter()
    snap = net.snapshot()
    res = net.service_result()
    problems = toolkit.check_telemetry(res, dep.wl, per * windows)
    if snap["clock"] != per * windows:
        problems.append(f"snapshot clock {snap['clock']} != {per * windows}")
    pk = snap["packets"]
    if pk["total"] != dep.wl.num_packets or pk["total"] != sum(
            pk[k] for k in ("pending", "in_flight", "delivered", "dropped")):
        problems.append(f"snapshot packet counts do not add up: {pk}")
    bad = mismatches(res, reference)
    if bad:
        problems.append(f"service result differs from one-shot run in {bad}")
    return t1 - t0, t2 - t1, problems


def sharded_phase(dep: Deployment, num_slices: int, num_shards: int):
    """``simulate_sharded`` against single-device ``simulate`` on the VLB
    deployment. Returns (cold s, warm s, mesh devices, problems)."""
    tables = FabricTables.build(dep.sched, dep.routings["vlb"])
    cfg = FabricConfig(**fabric_config())
    mesh, _ = fabric_mesh(num_shards)
    devices = list(mesh.devices.flat)
    problems = []
    if len({d.id for d in devices}) != num_shards:
        problems.append(f"mesh spans {devices}, not {num_shards} devices")
    t0 = time.perf_counter()
    res, dbg = simulate_sharded(tables, dep.wl, cfg, num_slices,
                                num_shards=num_shards, with_debug=True)
    t1 = time.perf_counter()
    again = simulate_sharded(tables, dep.wl, cfg, num_slices,
                             num_shards=num_shards)
    t2 = time.perf_counter()
    for d in devices:
        stats = d.memory_stats()
        if stats is not None and stats.get("peak_bytes_in_use", 0) <= 0:
            problems.append(f"{d} held no data during the sharded run")
    single = simulate(tables, dep.wl, cfg, num_slices)
    for name, other in (("warm repeat", again), ("simulate", single)):
        bad = mismatches(res, other)
        if bad:
            problems.append(f"simulate_sharded differs from {name} in {bad}")
    problems += toolkit.check_sharding(res, dbg, dep.wl, num_slices)
    return t1 - t0, t2 - t1, devices, problems


def _device(devs) -> dict:
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def _report(phase, dev, clock, problems, checked, **secs):
    times = " ".join(f"{k}={v:.3f}s" for k, v in secs.items())
    status = "FAIL " + "; ".join(problems) if problems else "ok"
    print(f"[{phase}] {dev['kind']} x{dev['count']} "
          f"compile={clock.lap():.3f}s {times} | {checked} | {status}",
          flush=True)


def _use_compile_cache():
    """JAX's persistent compile cache goes where JAX_COMPILATION_CACHE_DIR
    says; without it, to a fixed directory of the checkout, so later runs
    of this checkout find it."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(REPO / ".jax_cache"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only simulate_sharded over four chips")
    args = ap.parse_args(argv)
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {devs[0].platform} "
              f"({devs[0].device_kind})", file=sys.stderr)
        return 2
    if args.four_chips and len(devs) < 4:
        print(f"chip_smoke: --four-chips needs 4 TPU devices, found "
              f"{len(devs)}", file=sys.stderr)
        return 2
    _use_compile_cache()
    clock = CompileClock()
    jax.monitoring.register_event_duration_secs_listener(clock)
    dev = _device(devs)
    start = time.perf_counter()
    dep = rotor_deployment()
    print(f"[setup] {dev} {PAPER_TORS} ToRs, {dep.wl.num_packets} packets, "
          f"{NUM_SLICES} slices, host {time.perf_counter() - start:.1f}s",
          flush=True)
    clock.lap()
    failed = False

    if args.four_chips:
        cold, warm, devices, problems = sharded_phase(
            dep, NUM_SLICES, 4)
        _report("sharded-4", dev, clock, problems,
                f"mesh {[d.id for d in devices]}; == simulate, "
                "check_sharding clean", cold=cold, warm=warm)
        failed |= bool(problems)
    else:
        with concurrent.futures.ThreadPoolExecutor(max_workers=1) as pool:
            cpu = {s: pool.submit(cpu_reference, dep, s, NUM_SLICES)
                   for s in ("vlb", "ucmp")}
            results = {}
            for scheme in ("vlb", "ucmp"):
                res, cold, warm, problems = one_shot_phase(
                    dep, scheme, NUM_SLICES)
                results[scheme] = res
                _report(scheme, dev, clock, problems, _summary(res),
                        cold=cold, warm=warm)
                failed |= bool(problems)
            for scheme in ("vlb", "ucmp"):
                res, cold, warm, problems = one_shot_phase(
                    dep, scheme, NUM_SLICES, lookup_impl="pallas",
                    admit_impl="pallas")
                bad = mismatches(res, results[scheme])
                if bad:
                    problems.append(f"differs from jnp/xla run in {bad}")
                _report(f"{scheme}-pallas", dev, clock, problems,
                        "== jnp/xla run on the chip", cold=cold, warm=warm)
                failed |= bool(problems)
            first, rest, problems = service_phase(
                dep, NUM_SLICES, SERVICE_WINDOWS, results["vlb"])
            _report("service", dev, clock, problems,
                    "check_telemetry clean, snapshot adds up, == one-shot",
                    first_window=first, later_windows=rest)
            failed |= bool(problems)
            for scheme, fut in cpu.items():
                t0 = time.perf_counter()
                bad = mismatches(results[scheme], fut.result())
                problems = [f"TPU and CPU differ in {bad}"] if bad else []
                _report(f"{scheme}-cpu", dev, clock, problems,
                        "TPU result == CPU backend, every field",
                        wait=time.perf_counter() - t0)
                failed |= bool(problems)
    print(f"[total] {time.perf_counter() - start:.1f}s, backend compiles "
          f"{clock.total:.1f}s", flush=True)
    if failed:
        return 1
    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
