"""Run one cell of the benchmark once.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell of ``BENCHMARK.json`` joins a deployment (``bench/configs``) to a
traffic mix (``bench/traffic``); the mix names the entry point of the
program it drives (``bench/paths``). The run makes a pool of workloads
from the seed, deploys, warms up with one call (set-up), then calls the
entry point back to back for ``--seconds`` (the window), then compares
what the window produced with the plain reference (``bench/check.py``).

With ``--trace 0`` the last line of standard output carries the cell's
end-to-end metrics; with ``--trace 1`` the window runs under the profiler
and the line carries the per-layer metrics (``bench/metrics``), the
device's busy and window seconds and a ``breakdown``. The numbers compared
for ``correct`` are the last lines of standard error and the last key of
the line. A host without a TPU, or with fewer chips than the cell asks
for, gets a non-zero exit and no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]


@dataclasses.dataclass
class Context:
    """What a per-layer metric's reader may read."""

    spans: object          # harness.Spans
    window: tuple          # (start, end) on the host clock
    slices: int            # slices simulated in the window
    setup: dict            # routing_compile_s, jit_compile_s
    trace: dict | None     # trace.reduce of the window


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def device_info(chips: int) -> dict:
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak(chips: int) -> int:
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()[:chips]]
    return int(max(peaks))


def use_compile_cache():
    """JAX's persistent compile cache in ``.jax_cache/`` at the root of the
    checkout, whatever the environment names, so that two checkouts never
    share one; every program is cached, so only a checkout's first run
    compiles."""
    import jax
    (ROOT / ".jax_cache").mkdir(exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def run(args, spec: dict, start: float = T_START, trace_dir=None) -> dict:
    """Set-up, window and check of one cell on whatever devices JAX has.
    With ``--trace 1`` the profile goes to ``trace_dir`` and stays there, or
    to a temporary directory that is deleted."""
    import jax

    from bench import check, harness
    from bench import trace as trace_mod

    h = harness.Harness.open(spec, args.workload, args.seed)
    drv = harness.load_module("paths", h.mix["path"]).prepare(h)
    setup_s = time.perf_counter() - start
    h.setup["jit_compile_s"], compiles0 = h.clock.mark()

    outputs = []
    log_dir = None
    if args.trace:
        log_dir = trace_dir or tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(log_dir, profiler_options=opts)
    with h.spans("window"):
        t0 = time.perf_counter()
        while True:
            outputs.append(drv.call(len(outputs)))
            if time.perf_counter() - t0 >= args.seconds:
                break
        t1 = time.perf_counter()
    if args.trace:
        jax.profiler.stop_trace()
    window_compiles = h.clock.mark()[1] - compiles0
    calls = len(outputs)
    slices = calls * drv.slices_per_call
    memory = memory_peak(h.chips)
    e2e = {"pkt_slices_per_s": h.packets * slices / (t1 - t0),
           "setup_s": setup_s, **drv.end_to_end()}

    device = {**device_info(h.chips), "memory_peak_bytes": memory}
    line = {"metrics": {}, "device": device}
    if args.trace:
        red = trace_mod.reduce(trace_mod.newest_xplane(log_dir))
        if trace_dir is None:
            shutil.rmtree(log_dir, ignore_errors=True)
        ctx = Context(spans=h.spans, window=(t0, t1), slices=slices,
                      setup=h.setup, trace=red)
        device.update(busy_s=red["busy_s"], window_s=red["window_s"])
        line["breakdown"] = trace_mod.breakdown(red)
        metrics = [m for m in spec["per_layer"]
                   if args.workload in m.get("workloads", [args.workload])]
        for m in metrics:
            value = harness.load_module("metrics", m["name"]).read(ctx)
            if value is not None:
                line["metrics"][m["name"]] = {"value": value,
                                              "unit": m["unit"]}
    else:
        for m in spec["end_to_end"]:
            if args.workload in m.get("workloads", [args.workload]):
                line["metrics"][m["name"]] = {"value": e2e[m["name"]],
                                              "unit": m["unit"]}

    # the check runs once the window has closed, the peak memory has been
    # read and the program's state is freed
    checked = h.checked_calls(calls)
    kept = {i: outputs[i] for i in checked}
    del outputs
    drv.close()
    gc.collect()
    ref_tables = check.reference_tables(h.deployment)
    n_tables, table_notes = check.mismatches(h.deployed_tables(), ref_tables,
                                             check.TABLES)
    wls = {i: h.workload(i) for i in checked}
    refs = check.run_reference(h.deployment, ref_tables, wls, h.num_slices)
    nums, notes, bad_calls = check.compare(kept, refs, wls, h.num_slices)
    nums = {"tables": n_tables, **nums}
    correct, shown = check.verdict(nums)
    for note in table_notes + notes:
        print(f"mismatch: {note}", file=sys.stderr)
    print(f"window: {calls} calls, {slices} slices, {t1 - t0:.3f} s, "
          f"{window_compiles} compile events; checked calls {checked}",
          file=sys.stderr)
    for k, v in shown.items():
        print(f"check {k}: {v['value']} (limit {v['limit']})",
              file=sys.stderr)
    failed = len(checked) if n_tables else len(bad_calls)
    return {"correct": correct, "attempted": calls, "failed": failed,
            **line, "checks": shown}


def main(argv=None) -> int:
    args = parse(argv)
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    from bench import harness
    try:
        cell = harness.find_cell(spec, args.workload)
    except KeyError as e:
        print(f"bench: {e.args[0]}", file=sys.stderr)
        return 2
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < int(cell["chips"]):
        print(f"bench: cell {args.workload} needs {cell['chips']} TPU "
              f"chip(s); JAX found {len(devs)} {devs[0].platform} "
              f"device(s)", file=sys.stderr)
        return 3
    use_compile_cache()
    result = run(args, spec)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
