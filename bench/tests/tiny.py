"""Tiny sizes of the benchmark's cells for tests on the CPU: 8 ToRs, 2,048
packets at 5% load (so that they arrive over about 15 slices), 48-slice
runs (service: 6 steps of 8), every other setting as the cell's files
state it."""
from __future__ import annotations

import json

from bench import harness, run

SEED = 2**31 + 4321          # larger than 32 signed bits hold
NUM_SLICES = 48


def shrink(monkeypatch=None):
    """Make ``harness.load_json`` hand out tiny configs and mixes."""
    orig = harness.load_json

    def tiny(kind, name):
        d = orig(kind, name)
        if kind == "configs":
            d.update(tors=8, packets=2048)
        else:
            d.update(load=0.05, num_slices=NUM_SLICES)
            if d["path"] == "service":
                d.update(window_slices=8, steps=6)
        return d

    if monkeypatch is None:
        harness.load_json = tiny
    else:
        monkeypatch.setattr(harness, "load_json", tiny)


def spec(cell: str | None = None) -> dict:
    """``BENCHMARK.json``, with ``cell`` added where it is one of ``LATER``."""
    s = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    if cell in LATER:
        s["workloads"].append(LATER[cell])
    return s


# cells whose files are in bench/ but which BENCHMARK.json does not hold
# yet (PERF.md, Open questions)
LATER = {c["name"]: c for c in (
    {"name": "vlb_kv_shard4", "config": "rotor108_vlb",
     "traffic": "kv_shard4", "chips": 4},)}


def run_cell(cell: str, seed: int = SEED, seconds: float = 0.2,
             trace: int = 0, trace_dir=None) -> dict:
    """One run of ``cell`` past the look for a chip."""
    args = run.parse(["--workload", cell, "--seed", str(seed), "--seconds",
                      str(seconds), "--trace", str(trace)])
    return run.run(args, spec(cell), trace_dir=trace_dir)
