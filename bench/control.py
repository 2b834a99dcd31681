"""Readings of the control on the chip, at a cell's own size.

    python3 bench/control.py --workload <cell> [<cell> ...] --seeds 1 2 3 \
        [--calls 0 4 2]

The control is the plain reference with transit routes looked up in the
previous slice's time-flow table (which breaks a guarantee the deployment
states), put in the program's place: its result for one workload of each
seed's pool (the seed's entry of ``--calls``, default 0) goes through the same comparison as a
run's checked calls. Prints one JSON line per cell and
seed with the numbers compared; each line has to exceed a limit in at
least one number for the limits to separate a sound program from a
broken one. The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]


def control_outputs(h, ctl: dict, wl: dict) -> dict:
    """The control's result in the output form of the cell's path."""
    from bench import harness
    path = harness.load_module("paths", h.mix["path"])
    if hasattr(path, "control_outputs"):
        return path.control_outputs(ctl, wl, h.mix)
    return {"result": ctl}


def readings(spec: dict, cells: list[str], seed: int, call: int = 0):
    """One line per cell; cells whose deployment and workloads agree share
    one reference and one control run."""
    from bench import check, harness
    runs = {}
    for cell in cells:
        h = harness.Harness.open(spec, cell, seed)
        mix = {k: v for k, v in h.mix.items()
               if k not in ("path", "window_slices", "steps")}
        key = json.dumps([h.deployment, mix], sort_keys=True)
        wls = {call: h.workload(call)}
        if key not in runs:
            tables = check.reference_tables(h.deployment)
            t0 = time.perf_counter()
            ref = check.run_reference(h.deployment, tables, wls,
                                      h.num_slices)
            t1 = time.perf_counter()
            ctl = check.run_reference(h.deployment, tables, wls,
                                      h.num_slices, control=True)
            runs[key] = ref, ctl, t1 - t0
        ref, ctl, ref_s = runs[key]
        outs = {call: control_outputs(h, ctl[call], wls[call])}
        nums, _, _ = check.compare(outs, ref, wls, h.num_slices)
        correct, _ = check.verdict(nums)
        yield {"cell": cell, "seed": seed, "call": call,
               "control_correct": correct,
               **nums, "reference_s": ref_s}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", nargs="+", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--calls", type=int, nargs="+")
    args = ap.parse_args(argv)
    calls = args.calls or [0] * len(args.seeds)
    if len(calls) != len(args.seeds):
        ap.error("--calls needs one entry per seed")
    import jax
    if jax.devices()[0].platform != "tpu":
        print("control: needs a TPU", file=sys.stderr)
        return 3
    from bench.run import use_compile_cache
    use_compile_cache()
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    for seed, call in zip(args.seeds, calls):
        for line in readings(spec, args.workload, seed, call):
            print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
