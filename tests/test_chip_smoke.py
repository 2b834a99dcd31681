"""``chip_smoke.py`` on the CPU: its phase functions at 16 ToRs, with the
Pallas kernels in interpret mode, and its refusal to run without a TPU.

The script itself only runs on a TPU (the paper-scale 108-ToR phases); this
keeps the logic of every phase — the bit-for-bit comparisons, the service
checks, the sharded comparison — under the CPU suite.
"""
import importlib.util
import pathlib
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent
TORS, PACKETS, SLICES = 16, 1000, 48     # 48 slices > 3 cycles of 15
INTERPRET = dict(lookup_impl="pallas-interpret",
                 admit_impl="pallas-interpret")


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  REPO / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = mod
    spec.loader.exec_module(mod)
    yield mod
    del sys.modules["chip_smoke"]


@pytest.fixture(scope="module")
def dep(smoke):
    return smoke.rotor_deployment(TORS, max_packets=PACKETS)


@pytest.fixture(scope="module")
def vlb_result(smoke, dep):
    res, _cold, _warm, problems = smoke.one_shot_phase(dep, "vlb", SLICES)
    assert problems == []
    return res


def test_main_refuses_without_tpu(smoke, capsys):
    assert smoke.main([]) != 0
    assert smoke.main(["--four-chips"]) != 0
    out = capsys.readouterr()
    assert '"ok"' not in out.out
    assert "needs a TPU" in out.err


@pytest.mark.parametrize("scheme", ["vlb", "ucmp"])
def test_one_shot_pallas_and_cpu_phases(smoke, dep, scheme):
    res, cold, warm, problems = smoke.one_shot_phase(dep, scheme, SLICES)
    assert problems == [] and cold > 0 and warm > 0
    assert res.t_deliver.shape == (dep.wl.num_packets,)
    pal, _cold, _warm, problems = smoke.one_shot_phase(dep, scheme, SLICES,
                                                       **INTERPRET)
    assert problems == []
    assert smoke.mismatches(res, pal) == []
    assert smoke.mismatches(res, smoke.cpu_reference(dep, scheme,
                                                     SLICES)) == []


def test_mismatches_names_differing_fields(smoke, vlb_result):
    import dataclasses
    other = dataclasses.replace(vlb_result, nhops=vlb_result.nhops + 1)
    assert smoke.mismatches(vlb_result, other) == ["nhops"]


def test_service_phase(smoke, dep, vlb_result):
    first, rest, problems = smoke.service_phase(dep, SLICES, 4, vlb_result)
    assert problems == [] and first > 0 and rest > 0


def test_sharded_phase(smoke, dep, eight_devices):
    _cold, _warm, devices, problems = smoke.sharded_phase(dep, SLICES, 4)
    assert problems == []
    assert len({d.id for d in devices}) == 4
