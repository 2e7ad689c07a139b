"""The benchmark's own tests: CPU only, small sizes, outside the repository's
tier-1 suite (run them with ``python -m pytest bench/tests``)."""
import json
import os
import shutil
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

#: small stand-ins of the benchmark's configurations
TINY = {"paper-2500": {"n_pool": 300, "T": 8},
        "pool100k": {"n_pool": 1500, "T": 8}}


@pytest.fixture
def tiny_root(tmp_path):
    """A checkout root whose BENCHMARK.json is the real one and whose
    configuration files are the real ones cut to a CPU test's size."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for c in bench["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        cfg["n_pool"] = TINY[c["name"]]["n_pool"]
        cfg["protocol"]["T"] = TINY[c["name"]]["T"]
        dst = tmp_path / c["file"]
        dst.parent.mkdir(parents=True, exist_ok=True)
        dst.write_text(json.dumps(cfg))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp_path


@pytest.fixture
def bench_copy(tmp_path):
    """A copy of the benchmark directory that a test may add files to."""
    dst = tmp_path / "bench"
    shutil.copytree(BENCH, dst, ignore=shutil.ignore_patterns(
        "__pycache__", ".jax_cache", "tests"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    return dst
