"""The command refuses to run without a chip, and prints no result."""
import os
import subprocess
import sys

import benchtiny


def test_refuses_without_a_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "benchmarks/cells/run.py", "--workload", "stream-ycsb-b",
         "--seed", "2147483659", "--seconds", "10", "--trace", "0"],
        cwd=benchtiny.REPO, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_unknown_workload_fails():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "benchmarks/cells/run.py", "--workload", "no-such-cell",
         "--seed", "1", "--seconds", "10", "--trace", "0"],
        cwd=benchtiny.REPO, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""
