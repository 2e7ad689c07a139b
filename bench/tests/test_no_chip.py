"""Without a TPU the benchmark refuses to run and prints no result."""
import os
import subprocess
import sys

from conftest import ROOT


def test_run_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "paper-2500.single",
         "--seed", "3000000001", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert '"correct"' not in p.stdout


def test_run_exits_nonzero_with_only_the_benchmark_files(bench_copy):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "paper-2500.single",
         "--seed", "7", "--seconds", "1", "--trace", "0"],
        cwd=bench_copy.parent, env=env, capture_output=True, text=True,
        timeout=300)
    assert p.returncode != 0
    assert '"correct"' not in p.stdout
