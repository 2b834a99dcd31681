"""The trace reduction, on two small traces recorded on a TPU v5e at the
tests' tiny size and trimmed to two calls of the window
(``record_trace.py``). The expected numbers were worked out from the
trace's raw events in picoseconds, apart from the reducer; the reducer
reads nanoseconds, hence the tolerances."""
import pathlib

import pytest

from bench import harness, run, trace
from bench.tests import record_trace, tiny

DATA = pathlib.Path(__file__).resolve().parent / "data"
# window and busy seconds, admission-sort and idle shares in %
BY_HAND = {
    "vlb_kv_run": dict(window_s=0.054368536, busy_s=0.032011978516,
                       admit_sort_share=0.9570765575981746,
                       idle_share=41.12039633364415,
                       idle_metric="device_idle_share.run",
                       slice_metric="device_ms_per_slice"),
    "vlb_kv_service": dict(window_s=0.216394803, busy_s=0.035421899926,
                           admit_sort_share=0.8650591601245099,
                           idle_share=83.6308915764488,
                           idle_metric="device_idle_share.service",
                           slice_metric="device_ms_per_slice.service"),
}


@pytest.fixture(scope="module", params=sorted(BY_HAND))
def recorded(request):
    cell = request.param
    return cell, trace.reduce(DATA / f"{cell}.xplane.pb.gz"), BY_HAND[cell]


def test_busy_and_window_as_recorded(recorded):
    _, red, want = recorded
    assert red["devices"] == 1
    assert red["window_s"] == pytest.approx(want["window_s"], rel=1e-9)
    assert red["busy_s"] == pytest.approx(want["busy_s"], rel=1e-4)
    assert 0 < red["busy_s"] <= red["window_s"]


def test_breakdown_as_recorded(recorded):
    _, red, _ = recorded
    got = trace.breakdown(red)
    assert 0 < len(got["device_ops"]) <= 10
    assert len(got["idle_gaps"]) <= 10
    times = [t for _, t in got["device_ops"]]
    assert times == sorted(times, reverse=True)
    assert all(name.startswith("%") for name, _ in got["device_ops"])
    # leaf op time cannot exceed busy time, idle time fills the rest
    assert sum(red["op_s"].values()) <= red["busy_s"] * (1 + 1e-9)
    idle = sum(red["idle_s"].values())
    assert idle == pytest.approx(red["window_s"] - red["busy_s"], abs=1e-6)


def test_metrics_as_recorded(recorded):
    _, red, want = recorded
    slices = record_trace.CALLS * tiny.NUM_SLICES
    ctx = run.Context(spans=harness.Spans(), window=(0.0, 0.0),
                      slices=slices, setup={}, trace=red)
    read = lambda name: harness.load_module("metrics", name).read(ctx)
    assert read("admit_sort_share") == pytest.approx(
        want["admit_sort_share"], rel=1e-4)
    assert read(want["idle_metric"]) == pytest.approx(want["idle_share"],
                                                      rel=1e-4)
    assert read(want["slice_metric"]) == pytest.approx(
        1e3 * want["busy_s"] / slices, rel=1e-4)


def test_sort_rule_separates_admission_from_compaction():
    """The step's four HLO sorts: admission's two stable argsorts and the
    two unstable sorts of compaction's searchsorted, as read by hand."""
    red = trace.reduce(DATA / "vlb_kv_run.xplane.pb.gz")
    rule = harness.load_module("metrics", "admit_sort_share")
    sorts = {n.split(" =")[0]: rule.is_admission_sort(n)
             for n in red["op_s"] if trace.opcode(n) == "sort"}
    assert sorts == {"%sort.0": True, "%sort.2": True,
                     "%sort.27": False, "%sort.28": False}
