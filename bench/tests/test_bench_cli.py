"""The command the benchmark is run by refuses to measure without a TPU:
it exits non-zero and prints no result line."""
import os
import subprocess
import sys

from bench import cells


def test_no_tpu_exits_nonzero_without_a_result_line():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(cells.BENCH, "run.py"), "--workload",
         "fleet1k.aldpfl_sync", "--seed", str(2 ** 31 + 9), "--seconds", "1",
         "--trace", "0"],
        cwd=cells.ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "TPU" in proc.stderr


def test_unknown_workload_exits_nonzero():
    proc = subprocess.run(
        [sys.executable, os.path.join(cells.BENCH, "run.py"), "--workload",
         "no.such.cell", "--seed", "1", "--seconds", "1"],
        cwd=cells.ROOT, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0 and proc.stdout.strip() == ""
