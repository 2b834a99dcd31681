"""A run with the timed path broken underneath comes out not correct, for
each fault a cell can have: a step that returns its state unchanged, half
of the packets left out, an answer altered where it is produced. (The
exchange between chips left out is in ``test_bench_paths.py``.)"""
import dataclasses

import jax
import numpy as np
import pytest

from bench.tests import tiny  # noqa: I001  (puts the program on the path)
import repro.core.fabric as fabric
import repro.core.net as net

CELLS = ["vlb_kv_run", "vlb_kv_service"]


@pytest.fixture(autouse=True)
def _tiny(monkeypatch):
    tiny.shrink(monkeypatch)
    jax.clear_caches()
    yield
    jax.clear_caches()


def _state_unchanged(monkeypatch):
    make = fabric._make_step

    def frozen(*a, **k):
        step = make(*a, **k)

        def run(state, t):
            _, ys = step(state, t)
            return state, ys
        return run
    monkeypatch.setattr(fabric, "_make_step", frozen)


def _half(wl):
    keep = wl.num_packets // 2
    return dataclasses.replace(wl, **{
        f.name: getattr(wl, f.name)[:keep] for f in dataclasses.fields(wl)})


def _half_left_out(monkeypatch):
    sim, ingest = net.simulate, fabric.ingest
    monkeypatch.setattr(net, "simulate",
                        lambda tables, wl, *a, **k: sim(tables, _half(wl),
                                                        *a, **k))
    monkeypatch.setattr(fabric, "ingest",
                        lambda fs, wl: ingest(fs, _half(wl)))


def _answer_altered(monkeypatch):
    sim, fin = net.simulate, fabric.finalize

    def alter(res):
        res.t_deliver = res.t_deliver.copy()
        res.t_deliver[np.argmax(res.t_deliver)] += 1
        return res
    monkeypatch.setattr(net, "simulate", lambda *a, **k: alter(sim(*a, **k)))
    monkeypatch.setattr(fabric, "finalize", lambda fs: alter(fin(fs)))


FAULTS = {"state_unchanged": _state_unchanged,
          "half_left_out": _half_left_out,
          "answer_altered": _answer_altered}


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("cell", CELLS)
def test_fault_is_not_correct(cell, fault, monkeypatch):
    FAULTS[fault](monkeypatch)
    out = tiny.run_cell(cell)
    assert not out["correct"], out["checks"]
    assert out["failed"] > 0
