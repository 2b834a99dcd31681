"""Each path of the benchmark, at a tiny size on the CPU, agrees with the
copied reference, and its control does not."""
import os
import subprocess
import sys

import numpy as np
import pytest

from bench import check, control, harness
from bench.tests import tiny


@pytest.fixture(autouse=True)
def _tiny(monkeypatch):
    tiny.shrink(monkeypatch)


@pytest.mark.parametrize("cell", ["vlb_kv_run", "ucmp_kv_run",
                                  "vlb_kv_service"])
def test_path_agrees_with_reference(cell):
    out = tiny.run_cell(cell)
    assert out["correct"], out["checks"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert list(out)[-1] == "checks"
    names = {m["name"] for m in tiny.spec()["end_to_end"]
             if cell in m.get("workloads", [cell])}
    assert set(out["metrics"]) == names
    assert all(v["value"] > 0 for v in out["metrics"].values())
    want = {"tables", "results"} | (
        {"telemetry", "snapshots"} if "service" in cell else set())
    assert set(out["checks"]) == want


@pytest.mark.parametrize("cell", ["vlb_kv_run", "vlb_kv_service"])
def test_control_fails(cell):
    """The reference with transit routes looked up one slice stale, in the
    program's place, comes out not correct."""
    spec = tiny.spec(cell)
    h = harness.Harness.open(spec, cell, tiny.SEED)
    tables = check.reference_tables(h.deployment)
    wls = {i: h.workload(i) for i in range(2)}
    refs = check.run_reference(h.deployment, tables, wls, h.num_slices)
    ctl = check.run_reference(h.deployment, tables, wls, h.num_slices,
                              control=True)
    outs = {i: control.control_outputs(h, ctl[i], wls[i]) for i in wls}
    nums, _, _ = check.compare(outs, refs, wls, h.num_slices)
    correct, _ = check.verdict(nums)
    assert not correct and nums["results"] > 0


SHARDED = """
import json, sys
from bench.tests import tiny
tiny.shrink()
good = tiny.run_cell("vlb_kv_shard4")
import jax
jax.lax.psum = lambda x, axis_name, **_: x     # the exchange left out
jax.clear_caches()
bad = tiny.run_cell("vlb_kv_shard4")
print(json.dumps([good["correct"], good["checks"], bad["correct"]]))
"""


def test_sharded_path_on_four_host_devices():
    """``simulate_sharded`` over four forced host devices agrees with the
    reference; with the exchange between shards left out it does not."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join(
                   [str(harness.ROOT / "src"), str(harness.ROOT)]))
    proc = subprocess.run([sys.executable, "-c", SHARDED], env=env,
                          capture_output=True, text=True, timeout=600,
                          cwd=harness.ROOT)
    assert proc.returncode == 0, proc.stderr[-3000:]
    import json
    good, checks, bad = json.loads(proc.stdout.strip().splitlines()[-1])
    assert good, checks
    assert not bad
