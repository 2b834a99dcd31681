"""Benchmark harness — one module per paper table/figure (DESIGN.md §6).
Prints ``name,us_per_call,derived`` CSV. ``--quick`` runs reduced settings.
``--json`` additionally writes ``BENCH_<module>.json`` (name -> us/derived)
to the repo root so the perf trajectory is tracked across PRs (quick runs
write ``BENCH_<module>.quick.json`` to keep the baseline comparable).

``--check`` is the CI bench-regression gate: it runs each module in quick
mode ``--repeat`` times, takes the per-row *minimum* of ``us_per_call``
(minimum, not median: wall-clock noise on shared runners is strictly
additive, so the fastest repeat is the best estimate of the true cost),
and compares it against the committed baseline with a per-row tolerance
(``--tol``, default 1.3x). A committed quick-mode baseline
``BENCH_<module>.quick.json`` is preferred (quick-vs-quick compares the
full row set like-for-like); the full-run ``BENCH_<module>.json`` is the
fallback — quick settings are never *larger* than the full run's, so a
quick minimum exceeding ``tol x baseline`` is a genuine slowdown either
way. The gate exits non-zero and lists the offending rows. Rows whose
names only exist at full settings (e.g. ``route_ucmp_compile_108`` vs the
quick ``_32``) are skipped; rows not yet in the baseline are reported as
unbaselined but do not fail.

To intentionally re-baseline after a deliberate perf change::

    PYTHONPATH=src python -m benchmarks.run --json --only kernels_bench
    PYTHONPATH=src python -m benchmarks.run --json --quick --only fig_failover
    git add BENCH_kernels_bench.json BENCH_fig_failover.quick.json

and commit the refreshed JSON together with the change that explains it
(see also the benchmark table in README.md).
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys
import traceback

# the sharded-fabric rows (benchmarks/fabric_sharded.py) shard over forced
# host-platform CPU devices; the flag must land before jax first initializes
# (modules import jax lazily, inside _run_module). A caller-set count wins.
_DEVFLAG = "--xla_force_host_platform_device_count"
if _DEVFLAG not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "") + f" {_DEVFLAG}=8").strip()

REPO_ROOT = pathlib.Path(__file__).resolve().parent.parent

MODULES = [
    "fig8_fct",
    "fig9_transport",
    "fig_failover",
    "fig_skew",
    "fig10_slice_duration",
    "fig12_eqo",
    "fig13_udp_rtt",
    "table2_state",
    "table3_buffer",
    "table4_congestion",
    "min_slice",
    "kernels_bench",
    "fabric_sharded",
    "telemetry_overhead",
    "roofline",
]


def _run_module(name: str, quick: bool):
    mod = __import__(f"benchmarks.{name}", fromlist=["run"])
    return list(mod.run(quick=quick))


def _check(mods: list[str], tol: float, repeat: int) -> int:
    """Quick-run minima vs committed full baselines; 0 iff no regression."""
    failed = False
    for name in mods:
        # prefer a committed quick-mode baseline: quick-vs-quick is an
        # apples-to-apples row set (no rows skipped for existing only at
        # full settings) and a tighter gate than quick-vs-full minima
        base_path = REPO_ROOT / f"BENCH_{name}.quick.json"
        if not base_path.exists():
            base_path = REPO_ROOT / f"BENCH_{name}.json"
        if not base_path.exists():
            print(f"# {name}: no committed baseline ({base_path.name}), "
                  "skipping", file=sys.stderr)
            continue
        baseline = json.loads(base_path.read_text())
        samples: dict[str, list[float]] = {}
        derived: dict[str, str] = {}
        for _ in range(repeat):
            for n, us, d in _run_module(name, quick=True):
                samples.setdefault(n, []).append(us)
                derived[n] = str(d)
        print(f"# {name}: gate vs {base_path.name} (tol {tol:g}x, "
              f"min of {repeat})")
        for n, vals in samples.items():
            best = min(vals)
            if n not in baseline:
                print(f"{n},{best:.1f},unbaselined ({derived[n]})")
                continue
            ref = float(baseline[n]["us_per_call"])
            verdict = "ok" if best <= tol * ref else "REGRESSION"
            # derived metrics (e.g. failover recovery slices) are printed
            # for per-PR visibility but not compared: quick settings
            # legitimately change them (shorter runs, fewer epochs) — only
            # wall time has a sound one-sided quick-vs-full comparison
            print(f"{n},{best:.1f},{verdict} vs {ref:.1f} "
                  f"({best/max(ref, 1e-9):.2f}x) [{derived[n]}]")
            if verdict != "ok":
                failed = True
        missing = [n for n in baseline if n not in samples]
        if missing:
            print(f"# {name}: baseline rows not produced at quick settings "
                  f"(skipped): {missing}", file=sys.stderr)
    if failed:
        print("# BENCH REGRESSION: quick minimum exceeded tolerance; if the "
              "slowdown is intentional, re-baseline with "
              "`python -m benchmarks.run --json --only <module>` and commit "
              "the refreshed BENCH_*.json (see benchmarks/run.py docstring).",
              file=sys.stderr)
        return 1
    return 0


def _use_compile_cache() -> None:
    """JAX's persistent compile cache goes where JAX_COMPILATION_CACHE_DIR
    says; without it, to a fixed directory of the checkout, so later runs
    of this checkout find it."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        import jax
        jax.config.update("jax_compilation_cache_dir",
                          str(REPO_ROOT / ".jax_cache"))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default=None,
                    help="comma-separated module subset")
    ap.add_argument("--json", action="store_true",
                    help="write BENCH_<module>.json to the repo root")
    ap.add_argument("--check", action="store_true",
                    help="CI gate: quick-run minima vs committed "
                         "BENCH_<module>.json baselines; exit 1 on regression")
    ap.add_argument("--tol", type=float, default=1.3,
                    help="per-row tolerance factor for --check (default 1.3)")
    ap.add_argument("--repeat", type=int, default=3,
                    help="quick runs per module for the --check minimum")
    args = ap.parse_args()
    _use_compile_cache()
    mods = args.only.split(",") if args.only else MODULES
    if args.check:
        sys.exit(_check(mods, args.tol, args.repeat))
    print("name,us_per_call,derived")
    failed = []
    for name in mods:
        try:
            rows = []
            for row in _run_module(name, quick=args.quick):
                n, us, derived = row
                rows.append((n, us, derived))
                print(f"{n},{us:.1f},{derived}", flush=True)
            if args.json:
                payload = {n: {"us_per_call": round(us, 1), "derived": str(d)}
                           for n, us, d in rows}
                # quick runs use reduced settings — keep them out of the
                # tracked full-run baseline
                suffix = ".quick.json" if args.quick else ".json"
                out = REPO_ROOT / f"BENCH_{name}{suffix}"
                out.write_text(json.dumps(payload, indent=2) + "\n")
        except Exception:
            traceback.print_exc()
            failed.append(name)
    if failed:
        print(f"# FAILED: {failed}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
