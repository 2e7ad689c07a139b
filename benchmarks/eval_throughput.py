"""Evaluator throughput — the quantitative argument for batching the "VLSI
flow" onto the accelerator (DESIGN.md §3).

The paper's evaluator is days of RTL flow per design; ours is a batched XLA
program. This bench measures designs/second through the jnp evaluator (and
through the Pallas systolic_eval path in interpret mode for correctness —
interpret timing is meaningless, noted in output).
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import make_space
from repro.soc import get_workload, soc_metrics
from .common import write_csv


def main(n: int = 2500, workload: str = "resnet50", verbose: bool = True):
    space = make_space()
    idx = np.asarray(space.sample(jax.random.PRNGKey(0), n))
    vals = jnp.asarray(space.values(idx), jnp.float32)
    layers = jnp.asarray(get_workload(workload), jnp.float32)
    soc_metrics(vals[:8], layers).block_until_ready()  # compile
    t0 = time.time()
    reps = 5
    for _ in range(reps):
        y = soc_metrics(vals, layers)
    y.block_until_ready()
    dt = (time.time() - t0) / reps
    rate = n / dt
    rows = [["jnp_batched", n, round(dt * 1e3, 2), round(rate, 1)]]
    path = write_csv("eval_throughput.csv",
                     ["path", "designs", "ms_per_sweep", "designs_per_s"],
                     rows)
    if verbose:
        print(f"# evaluator throughput ({workload}, {n} designs)")
        print(f"  jnp batched sweep: {dt*1e3:.1f} ms  "
              f"({rate:,.0f} designs/s on CPU; paper's VLSI flow: "
              f"~1 design/hours)")
        print(f"  csv: {path}")
    return rate


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=2500)
    ap.add_argument("--workload", default="resnet50")
    a = ap.parse_args()
    main(a.n, a.workload)
