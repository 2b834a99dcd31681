"""The reduction of the program's own scopes and spans
(``bench/program_trace.py``), on two small traces recorded on a TPU v5e
(``record_scoped_trace.py``) and on PR 13's trace of a program that had
none. The expected numbers were worked out from the trace's raw events in
picoseconds, apart from the reducer, with the HLO read through its own
schema; the reducer reads nanoseconds, hence the tolerances."""
import pathlib

import pytest

from bench import harness, program_trace, run, trace
from repro.core import tracing

DATA = pathlib.Path(__file__).resolve().parent / "data"
# shares of busy time in %, host ms per run call
BY_HAND = {
    "vlb_kv_run": dict(backlog_filter_share=14.924602841519146,
                       lookup_share=8.136657960793308,
                       admit_share=8.547453433615521,
                       compact_share=48.273479045774124,
                       run_host_ms=9.867370000000001,
                       scoped_share=94.79391039758437),
    "ucmp_kv_run": dict(backlog_filter_share=18.144081516893397,
                        lookup_share=7.200695473932967,
                        admit_share=7.235048816342004,
                        compact_share=46.104264147879384,
                        run_host_ms=8.9307,
                        scoped_share=93.79757858053485),
}
RUN_SPANS = ("OpenOpticsNet.run", "run.tables", "run.to_device",
             "run.dispatch", "run.device_wait", "run.result_copy",
             "run.traffic_matrix")


def _context(path):
    red = {**trace.reduce(path), **program_trace.reduce(path)}
    return run.Context(spans=harness.Spans(), window=(0.0, 0.0), slices=0,
                       setup={}, trace=red)


def _read(ctx, name):
    return harness.load_module("metrics", name).read(ctx)


@pytest.fixture(scope="module", params=sorted(BY_HAND))
def recorded(request):
    cell = request.param
    return cell, _context(DATA / f"{cell}.scoped.xplane.pb.gz"), \
        BY_HAND[cell]


def test_metrics_as_recorded(recorded):
    _, ctx, want = recorded
    for name in program_trace.PROGRAM_METRICS:
        assert _read(ctx, name) == pytest.approx(want[name], rel=5e-4), name


def test_scopes_cover_the_busy_time(recorded):
    _, ctx, want = recorded
    scope_s = ctx.trace["scope_s"]
    assert set(scope_s) <= {"fabric", *tracing.SCOPES}
    # each phase of the step ran in the window (the compact views too:
    # 4,096 packets)
    assert set(scope_s) == {"fabric", *tracing.SCOPES} - {"fabric/finish"}
    assert 100 * sum(scope_s.values()) / ctx.trace["busy_s"] == \
        pytest.approx(want["scoped_share"], rel=5e-4)
    assert sum(scope_s.values()) <= sum(ctx.trace["op_s"].values())


def test_program_spans_as_recorded(recorded):
    _, ctx, _ = recorded
    red = ctx.trace
    assert red["span_n"] == {name: 2 for name in RUN_SPANS}
    span_s = red["span_s"]
    assert span_s["run.device_wait"] < span_s["OpenOpticsNet.run"]
    # every idle gap of the window lies in a span of the program, and the
    # gaps fill the window's idle time
    assert program_trace.OUTSIDE not in red["idle_s"]
    assert all(program_trace.PROGRAM_SPAN.match(k) for k in red["idle_s"])
    assert sum(red["idle_s"].values()) == pytest.approx(
        red["window_s"] - red["busy_s"], abs=1e-6)


def test_a_program_without_scopes_reads_nothing():
    ctx = _context(DATA / "vlb_kv_run.xplane.pb.gz")
    assert ctx.trace["scope_s"] == {} and ctx.trace["span_n"] == {}
    for name in program_trace.PROGRAM_METRICS:
        assert _read(ctx, name) is None, name


@pytest.mark.parametrize("op_name,paths", [
    ("jit(_simulate_jit)/while/body/closed_call/fabric/hop/cond/"
     "branch_1_fun/admit/jit(argsort)/sort", ["fabric/hop/admit"]),
    ("fabric/dynamic_slice", ["fabric"]),
    ("fabric/hop/compact/fabric/hop/compact/fabric/inject/compact",
     ["fabric/hop/compact", "fabric/hop/compact", "fabric/inject/compact"]),
    ("jit(_simulate_jit)/while/body/dynamic_update_slice", []),
])
def test_scope_paths(op_name, paths):
    assert program_trace.scope_paths(op_name) == paths


def test_a_shared_op_takes_the_call_site_around_it():
    shared = "fabric/hop/compact/fabric/inject/compact/fabric/hop/compact"
    names = {"%cond.1": "fabric/inject/cond", "%cond.2": "fabric/hop/cond",
             "%sort.1": shared, "%copy.1": None}

    def candidates(name):
        op = names[name.split(" ")[0]]
        return (program_trace.scope_paths(op) if op else [], op is not None)

    ev = [(0, 10, "%cond.1 = inject"), (1, 5, "%sort.1 = a"),
          (5, 6, "%copy.1 = b"), (20, 30, "%cond.2 = hop"),
          (21, 25, "%sort.1 = a"), (40, 41, "%sort.1 = a")]
    got = [sc for _, _, sc in program_trace._leaf_scopes(ev, candidates)]
    assert got == ["fabric/inject/compact", "fabric/inject",
                   "fabric/hop/compact", "fabric/hop/compact"]


def test_cli_needs_a_tpu(capsys):
    assert program_trace.main(["--workload", "vlb_kv_run", "--seed", "1",
                               "--seconds", "1"]) == 3
    assert "needs 1 TPU" in capsys.readouterr().err
