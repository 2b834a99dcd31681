"""The command line: an unknown cell fails, a host without a TPU gets a
non-zero exit and no result line."""
import os
import subprocess
import sys

from bench import harness, run


def test_unknown_cell_fails(capsys):
    rc = run.main(["--workload", "no_such_cell", "--seed", "1",
                   "--seconds", "1", "--trace", "0"])
    assert rc != 0
    out, err = capsys.readouterr()
    assert out == "" and "unknown cell" in err


def test_no_tpu_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "vlb_kv_run",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        env=env, capture_output=True, text=True, timeout=300,
        cwd=harness.ROOT)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "needs 1 TPU" in proc.stderr
