"""The benchmark's machinery, shared by every cell: loading a cell's files,
the deployment through the program's user API, host spans, JAX's compile
events, the measured window and the correctness check.

Everything that belongs to one deployment, traffic mix, path or per-layer
metric sits in a file of its own and is found by name:

* ``bench/configs/<config>.json``: the deployment (sizes, routing, fabric);
* ``bench/traffic/<traffic>.json``: the mix, read by :mod:`bench.gen`; its
  ``path`` names the driver;
* ``bench/paths/<path>.py``: the driver of one entry point of the program.
  ``prepare(h)`` deploys and warms up, and returns an object with
  ``slices_per_call``, ``call(i)`` (one timed unit of work on workload i,
  returning what the check compares: ``result`` and, where the path has
  them, ``telemetry`` and ``snapshots``), ``end_to_end()`` (the path's own
  end-to-end metrics) and ``close()``. A path whose output holds more than
  ``result`` also has ``control_outputs(ctl, wl, mix)``, the same form
  built from a reference result, for ``bench/control.py``;
* ``bench/metrics/<metric>.py``: ``read(ctx)`` returns the per-layer
  metric's value, or ``None`` where the run has nothing to read;
* ``bench/reference/schedules/<schedule>.py`` and
  ``bench/reference/schemes/<scheme>.py``: the plain reference of the
  deployment's optical schedule and routing scheme, named by its config
  (found by :func:`bench.reference.tables_ref.deployment_tables`).
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import pathlib
import time

import numpy as np

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent

# workloads made per run: the window cycles through them, so no two calls
# in a row see the same input
POOL = 16
# calls of the window compared with the reference, drawn from the seed (the
# reference takes longer than the window on the chip; see PERF.md)
CHECKED_CALLS = 1

COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")
BACKEND_COMPILE = COMPILE_EVENTS[2]


def load_module(kind: str, name: str):
    """``bench/<kind>/<name>.py`` as a module (names may hold dots)."""
    path = BENCH / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} file {path.relative_to(ROOT)}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(kind: str, name: str) -> dict:
    path = BENCH / kind / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} file {path.relative_to(ROOT)}")
    return json.loads(path.read_text())


def find_cell(spec: dict, name: str) -> dict:
    for cell in spec["workloads"]:
        if cell["name"] == name:
            return cell
    raise KeyError(f"unknown cell {name!r}; BENCHMARK.json has "
                   f"{[c['name'] for c in spec['workloads']]}")


class Spans:
    """Host spans: each ``with spans("name")`` is timed on the host clock
    and is also a ``jax.profiler.TraceAnnotation``, so that a trace shows
    what the host was doing while the device sat idle."""

    def __init__(self):
        import jax
        self._annotate = jax.profiler.TraceAnnotation
        self.records: list[tuple[str, float, float]] = []

    @contextlib.contextmanager
    def __call__(self, name: str):
        t0 = time.perf_counter()
        with self._annotate(name):
            yield
        self.records.append((name, t0, time.perf_counter()))

    def seconds(self, name: str, since: float = float("-inf")) -> list[float]:
        return [b - a for n, a, b in self.records if n == name and a >= since]


class CompileClock:
    """JAX's compile events (tracing, lowering, backend compile or cache
    load), summed per phase of the run."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.backend_events = 0
        jax.monitoring.register_event_duration_secs_listener(self)

    def __call__(self, event, duration, **_):
        if event in COMPILE_EVENTS:
            self.seconds += duration
        if event == BACKEND_COMPILE:
            self.backend_events += 1

    def mark(self) -> tuple[float, int]:
        return self.seconds, self.backend_events


@dataclasses.dataclass
class Harness:
    """One run of one cell."""

    cell: dict
    deployment: dict
    mix: dict
    seed: int
    chips: int
    spans: Spans
    clock: CompileClock
    workloads: list = dataclasses.field(default_factory=list)
    setup: dict = dataclasses.field(default_factory=dict)

    @classmethod
    def open(cls, spec: dict, cell_name: str, seed: int) -> "Harness":
        from . import gen
        cell = find_cell(spec, cell_name)
        dep = load_json("configs", cell["config"])
        mix = load_json("traffic", cell["traffic"])
        h = cls(cell=cell, deployment=dep, mix=mix, seed=seed,
                chips=int(cell["chips"]), spans=Spans(),
                clock=CompileClock())
        h.workloads = gen.pool(dep, mix, seed, POOL)
        return h

    @property
    def packets(self) -> int:
        return int(self.deployment["packets"])

    @property
    def num_slices(self) -> int:
        return int(self.mix["num_slices"])

    def workload(self, i: int) -> dict:
        return self.workloads[i % POOL]

    def checked_calls(self, n_calls: int) -> list[int]:
        """The calls whose outputs are compared, drawn from the seed."""
        rng = np.random.default_rng([self.seed, 1])
        k = min(CHECKED_CALLS, n_calls)
        return sorted(int(i) for i in rng.choice(n_calls, k, replace=False))

    # -- the program ------------------------------------------------------
    def deploy(self, telemetry=None):
        """The deployment through the user API: ``deploy_topo`` with the
        schedule and ``deploy_routing`` with the routing scheme's tables.
        Routing compile is timed once, as set-up."""
        import repro.core as core
        dep = self.deployment
        if "schedule" not in self.setup:
            sched = getattr(core, dep["schedule"])(
                dep["tors"], dep["uplinks"], slice_us=dep["slice_us"])
            routing = dict(dep["routing"])
            scheme = getattr(core, routing.pop("scheme"))
            t0 = time.perf_counter()
            self.setup["routing"] = scheme(sched, **routing)
            self.setup["routing_compile_s"] = time.perf_counter() - t0
            self.setup["schedule"] = sched
        cfg = dict(node="rack", node_num=dep["tors"], uplink=dep["uplinks"],
                   slice_us=dep["slice_us"], fabric=dict(
                       slice_bytes=dep["slice_bytes"], **dep["fabric"]))
        if telemetry is not None:
            cfg["telemetry"] = telemetry
        net = core.OpenOpticsNet(cfg)
        if not net.deploy_topo(self.setup["schedule"]):
            raise RuntimeError(f"{dep['schedule']} schedule failed deploy_topo")
        net.deploy_routing(self.setup["routing"])
        return net

    def deployed_tables(self) -> dict:
        """The tables the program deployed, as the fabric executes them."""
        from repro.core import FabricTables
        ft = FabricTables.build(self.setup["schedule"], self.setup["routing"])
        return {k: np.asarray(getattr(ft, k)) for k in
                ("conn", "tf_next", "tf_dep", "inj_next", "inj_dep",
                 "first_direct")}

    def program_workload(self, i: int):
        from repro.core import Workload
        return Workload(**self.workload(i))
