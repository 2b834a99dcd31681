"""How ``correct`` is decided: what the timed path produced, at the timed
sizes, against the benchmark's own plain reference (``bench/reference``),
which imports nothing of the program and builds its own tables.

Numbers compared, each with its limit (all exact, so every limit is 0):

* ``tables``: table entries the program deployed that differ from the
  reference's schedule and routing tables;
* ``results``: elements of the checked calls' fabric results that differ
  from the reference run on the same workload;
* ``telemetry``: violations of the counters against the host replay of the
  reference's terminal state (service path);
* ``snapshots``: snapshot packet and byte totals that differ from the
  reference at their clock (service path).

The control puts the reference in the program's place with transit
routes looked up in the previous slice's time-flow table, which breaks a
guarantee the deployment states; it has to fail.

The reference's results are kept in ``.bench_cache/`` inside the checkout,
keyed by a hash of the reference's code (every ``.py`` under
``bench/reference/``) and of everything it is given, so a later run on the
same seed (the second set of a measurement) reads them back instead of
running the reference again.
"""
from __future__ import annotations

import hashlib
import os
import pathlib

import numpy as np

from .reference import fabric_ref, tables_ref, telemetry_ref

CACHE = pathlib.Path(__file__).resolve().parent.parent / ".bench_cache"

LIMITS = {"tables": 0, "results": 0, "telemetry": 0, "snapshots": 0}
TABLES = ("conn", "tf_next", "tf_dep", "inj_next", "inj_dep", "first_direct")


def reference_config(deployment: dict, control: bool = False):
    fab = deployment["fabric"]
    fields = {f: fab[f] for f in fabric_ref.RefConfig.__dataclass_fields__
              if f in fab}
    if control:
        fields["table_lag"] = 1
    return fabric_ref.RefConfig(slice_bytes=deployment["slice_bytes"],
                                **fields)


def mismatches(got: dict, want: dict, fields) -> tuple[int, list[str]]:
    """Elements of ``fields`` that differ between two dicts of arrays (a
    field of another shape counts every element of the larger one)."""
    count, where = 0, []
    for k in fields:
        a, b = np.asarray(got[k]), np.asarray(want[k])
        n = int((a != b).sum()) if a.shape == b.shape else max(a.size, b.size)
        if n:
            count += n
            where.append(f"{k}: {n}")
    return count, where


def compare(outputs: dict, refs: dict, workloads: dict,
            num_slices: int) -> tuple[dict, list[str], set]:
    """``outputs[i]`` (a path's output of call i) against ``refs[i]`` (the
    reference result on ``workloads[i]``). Returns the numbers compared,
    what differed, and the calls that differed."""
    nums = {"results": 0}
    notes: list[str] = []
    failed = set()
    for i, out in outputs.items():
        before = len(notes)
        n, where = mismatches(out["result"], refs[i],
                              fabric_ref.RESULT_FIELDS)
        nums["results"] += n
        notes += [f"call {i} result {w}" for w in where]
        if "telemetry" in out:
            bad = telemetry_ref.check_counters(
                out["telemetry"], refs[i], workloads[i], num_slices)
            nums["telemetry"] = nums.get("telemetry", 0) + len(bad)
            notes += [f"call {i} telemetry: {b}" for b in bad[:8]]
        if "snapshots" in out:
            bad = [b for s in out["snapshots"] for b in
                   telemetry_ref.check_snapshot(s, refs[i], workloads[i])]
            nums["snapshots"] = nums.get("snapshots", 0) + len(bad)
            notes += [f"call {i} snapshot: {b}" for b in bad[:8]]
        if len(notes) > before:
            failed.add(i)
    return nums, notes, failed


def run_reference(deployment: dict, ref_tables: dict, workloads: dict,
                  num_slices: int, control: bool = False) -> dict:
    cfg = reference_config(deployment, control)
    return {i: _cached_reference(ref_tables, wl, cfg, num_slices)
            for i, wl in workloads.items()}


def reference_code() -> bytes:
    """A hash of every ``.py`` under ``bench/reference/``, at any depth, with
    its path, so that a new or changed schedule or scheme file changes the
    key of every cached result."""
    key = hashlib.sha256()
    for f in sorted(tables_ref.REFERENCE.rglob("*.py")):
        key.update(f.relative_to(tables_ref.REFERENCE).as_posix().encode())
        key.update(f.read_bytes())
    return key.digest()


def _cached_reference(tables: dict, wl: dict, cfg, num_slices: int) -> dict:
    key = hashlib.sha256(reference_code())
    key.update(repr((cfg, num_slices)).encode())
    for name, a in sorted({**tables, **wl}.items()):
        a = np.ascontiguousarray(a)
        key.update(f"{name} {a.dtype} {a.shape}".encode())
        key.update(a.tobytes())
    path = CACHE / f"{key.hexdigest()}.npz"
    if path.is_file():
        with np.load(path) as z:
            return {k: z[k] for k in z.files}
    out = fabric_ref.simulate_ref(tables, wl, cfg, num_slices)
    CACHE.mkdir(exist_ok=True)
    tmp = path.with_suffix(f".{os.getpid()}.tmp")
    with open(tmp, "wb") as fh:
        np.savez(fh, **out)
    tmp.replace(path)
    return out


def reference_tables(deployment: dict) -> dict:
    return tables_ref.deployment_tables(deployment)


def verdict(nums: dict) -> tuple[bool, dict]:
    """``correct`` and the numbers beside their limits."""
    shown = {k: {"value": v, "limit": LIMITS[k]} for k, v in nums.items()}
    return all(v <= LIMITS[k] for k, v in nums.items()), shown
