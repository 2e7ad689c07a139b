"""Fig. 7(a) — ADRS vs exploration round for all methods (+ 7(b) breakdown).

Protocol (§IV-B): identical evaluation budget per method (b init + T BO
rounds), repeated over seeds, mean ADRS against the pool's true front.

The multi-seed SoC-Tuner curves run through the fleet path (one batched
``fleet_tuner`` call for all seeds, shared evaluation cache) unless
``--use-kernels`` forces the sequential Pallas-kernel loop; baselines remain
per-seed sequential runs.
"""
from __future__ import annotations

import argparse
import time

import numpy as np

from .common import METHODS, make_bench, run_fleet, run_method, write_csv


def main(T: int = 20, b: int = 20, n: int = 30, repeats: int = 3,
         n_pool: int = 2500, workload: str = "resnet50",
         methods=METHODS, verbose: bool = True, use_kernels: bool = False):
    bench = make_bench(workload, n_pool=n_pool)
    rows, summary = [], {}
    for m in methods:
        curves = []
        t0 = time.time()
        if m == "soc-tuner" and not use_kernels:
            fr = run_fleet([bench], repeats, T=T, b=b, n=n)
            curves = [[h["adrs"] for h in r.history] for r in fr.results]
            if verbose:
                print(f"  {m}: fleet of {repeats} seeds, {fr.cache.summary()}")
        else:
            for s in range(repeats):
                res = run_method(m, bench, T=T, b=b, n=n, seed=s,
                                 use_kernels=use_kernels)
                curves.append([h["adrs"] for h in res.history])
        curves = np.asarray(curves)
        mean = curves.mean(0)
        for r, v in enumerate(mean):
            rows.append([m, r, round(float(v), 5)])
        summary[m] = (float(mean[-1]), time.time() - t0)
        if verbose:
            print(f"  {m:<12s} final ADRS {mean[-1]:.4f} "
                  f"(start {mean[0]:.4f}) [{summary[m][1]:.0f}s]")
    path = write_csv("fig7a_adrs.csv", ["method", "round", "adrs"], rows)
    if verbose:
        best = min(summary, key=lambda k: summary[k][0])
        print(f"  best: {best}; csv: {path}")
    return summary


def breakdown(workload: str = "resnet50", T: int = 20, b: int = 20,
              n: int = 30, verbose: bool = True):
    """Fig. 7(b): area breakdown of the balanced optimum SoC-Tuner picks."""
    import jax.numpy as jnp
    from repro.soc.model import area_breakdown
    bench = make_bench(workload)
    res = run_method("soc-tuner", bench, T=T, b=b, n=n, seed=0)
    # balanced choice: min normalized L2 over the learned front
    front = res.pareto_y
    z = (front - front.min(0)) / np.maximum(np.ptp(front, 0), 1e-12)
    pick = int(np.argmin(np.linalg.norm(z, axis=1)))
    idx = res.pareto_idx(bench.pool)[pick]
    vals = bench.space.values(idx[None, :])
    parts = area_breakdown(jnp.asarray(vals, jnp.float32))
    total = float(sum(v[0] for v in parts.values()))
    rows = [[k, round(float(v[0]), 4), round(float(v[0]) / total * 100, 1)]
            for k, v in sorted(parts.items(), key=lambda kv: -kv[1][0])]
    path = write_csv("fig7b_breakdown.csv", ["component", "mm2", "pct"], rows)
    if verbose:
        print(f"# Fig7b area breakdown of the chosen optimum "
              f"(lat={front[pick,0]:.3f}ms p={front[pick,1]:.1f}mW "
              f"a={front[pick,2]:.2f}mm2)")
        for r in rows:
            print(f"  {r[0]:<14s} {r[1]:8.4f} mm2  {r[2]:5.1f}%  "
                  + "#" * int(r[2] / 2))
        print(f"  csv: {path}")
    return rows


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--T", type=int, default=20)
    ap.add_argument("--b", type=int, default=20)
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--pool", type=int, default=2500)
    ap.add_argument("--workload", default="resnet50")
    ap.add_argument("--breakdown", action="store_true")
    ap.add_argument("--use-kernels", action="store_true")
    a = ap.parse_args()
    if a.breakdown:
        breakdown(a.workload, T=a.T, b=a.b)
    else:
        main(T=a.T, b=a.b, repeats=a.repeats, n_pool=a.pool,
             workload=a.workload, use_kernels=a.use_kernels)
