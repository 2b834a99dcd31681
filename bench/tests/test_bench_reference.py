"""The table reference: a deployment's schedule and routing scheme are found
by the names its config gives, each in a file of its own, and agree with
what the program deploys."""
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from bench import check, harness
from bench.reference import tables_ref

CONFIGS = ("rotor108_vlb", "rotor108_ucmp")


@pytest.fixture
def reference_copy(tmp_path, monkeypatch):
    """A copy of ``bench/reference`` that the reference and the check's
    cache key read in its place."""
    root = tmp_path / "bench" / "reference"
    shutil.copytree(tables_ref.REFERENCE, root,
                    ignore=shutil.ignore_patterns("__pycache__"))
    monkeypatch.setattr(tables_ref, "REFERENCE", root)
    return root


def test_a_scheme_file_is_found_by_its_name(reference_copy):
    """A scheme under a new name is picked up from its file alone, and the
    file enters the key of the cached reference results."""
    dep = dict(harness.load_json("configs", "rotor108_vlb"), tors=8)
    before = check.reference_code()
    new = reference_copy / "schemes" / "vlb_again.py"
    shutil.copy(reference_copy / "schemes" / "vlb.py", new)
    added = check.reference_code()
    assert added != before
    want = tables_ref.deployment_tables(dep)
    dep["routing"] = dict(dep["routing"], scheme="vlb_again")
    got = tables_ref.deployment_tables(dep)
    assert check.mismatches(got, want, check.TABLES)[0] == 0
    new.write_text(new.read_text() + "\n# edited\n")
    assert check.reference_code() != added


@pytest.mark.parametrize("kind,field", [("schedules", "schedule"),
                                        ("schemes", "routing")])
def test_a_name_with_no_file_names_the_file(kind, field):
    dep = dict(harness.load_json("configs", "rotor108_vlb"), tors=8)
    dep[field] = ({"scheme": "no_such"} if field == "routing" else "no_such")
    with pytest.raises(FileNotFoundError,
                       match=f"bench/reference/{kind}/no_such.py"):
        tables_ref.deployment_tables(dep)


BLOCKED = """
import sys

class Block:
    def find_spec(self, name, *_):
        if name == "repro" or name.startswith("repro."):
            raise ImportError(f"the reference imported {name}")

sys.meta_path.insert(0, Block())
import json
from bench.reference import fabric_ref, tables_ref, telemetry_ref
for name in ("rotor108_vlb", "rotor108_ucmp"):
    dep = json.load(open(f"bench/configs/{name}.json"))
    tables_ref.deployment_tables(dict(dep, tors=8))
print("ok")
"""


def test_the_reference_imports_nothing_of_the_program():
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(harness.ROOT))
    proc = subprocess.run([sys.executable, "-c", BLOCKED], env=env,
                          capture_output=True, text=True, timeout=300,
                          cwd=harness.ROOT)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert proc.stdout.strip() == "ok"


@pytest.mark.parametrize("uplinks", [1, 2, 6])
def test_reference_tables_match_the_program(uplinks):
    """The deployed ``conn``, ``tf_*``, ``inj_*`` and ``first_direct`` of
    each configured scheme equal the reference's, at 16 ToRs."""
    for name in CONFIGS:
        dep = dict(harness.load_json("configs", name), tors=16,
                   uplinks=uplinks)
        h = harness.Harness(cell={}, deployment=dep, mix={}, seed=0,
                            chips=1, spans=None, clock=None)
        h.deploy()
        got = h.deployed_tables()
        want = tables_ref.deployment_tables(dep)
        assert got["conn"].shape == (15, 16, uplinks)
        n, where = check.mismatches(got, want, check.TABLES)
        assert n == 0, (name, where)
        assert np.any(want["tf_next"] >= 0)
