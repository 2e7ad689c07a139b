"""Fig. 4 — learned Pareto sets in latency-area / latency-power space, and
the simplified-model gap (Fig. 4c).

(a,b): each method's learned front vs the pool's true front.
(c): explore with the SCALE-Sim-like simplified model, then re-evaluate its
"optimal" picks with the full flow — the gap between where the simplified
model *thinks* its designs land and where they actually land.
"""
from __future__ import annotations

import argparse

import numpy as np

from repro.core import adrs
from .common import make_bench, run_method, write_csv


def main(T: int = 20, b: int = 20, n: int = 30,
         methods=("soc-tuner", "microal", "random"),
         workload: str = "resnet50", n_pool: int = 2500,
         verbose: bool = True):
    bench = make_bench(workload, n_pool=n_pool)
    rows = [["true-front", i, *map(float, y)]
            for i, y in enumerate(bench.ref_front)]
    out = {}
    for m in methods:
        res = run_method(m, bench, T=T, b=b, n=n, seed=0)
        for i, y in enumerate(res.pareto_y):
            rows.append([m, i, *map(float, y)])
        out[m] = adrs(bench.ref_front, res.pareto_y)
        if verbose:
            print(f"  {m:<12s} front size {len(res.pareto_y):3d} "
                  f"ADRS {out[m]:.4f}")
    path = write_csv("fig4ab_pareto.csv",
                     ["method", "i", "latency_ms", "power_mw", "area_mm2"],
                     rows)
    if verbose:
        print(f"  csv: {path}")
    return out


def simplified_gap(T: int = 20, b: int = 20, n: int = 30,
                   workload: str = "resnet50", n_pool: int = 2500,
                   verbose: bool = True):
    """Fig. 4(c): the simplified model misguides exploration."""
    bench_full = make_bench(workload, n_pool=n_pool)
    bench_simp = make_bench(workload, n_pool=n_pool, simplified=True)
    res = run_method("soc-tuner", bench_simp, T=T, b=b, n=n, seed=0)
    picks = res.pareto_idx(bench_simp.pool)
    believed = res.pareto_y                       # what the model claimed
    actual = np.asarray(bench_full.flow_factory()(picks))  # ground truth
    rows = []
    for i in range(len(picks)):
        rows.append(["believed", i, *map(float, believed[i])])
        rows.append(["actual", i, *map(float, actual[i])])
    path = write_csv("fig4c_simplified_gap.csv",
                     ["kind", "i", "latency_ms", "power_mw", "area_mm2"],
                     rows)
    gap = float(np.mean(np.abs(actual - believed)
                        / np.maximum(np.abs(actual), 1e-9)))
    adrs_simp = adrs(bench_full.ref_front, actual)
    bench = bench_full
    res_full = run_method("soc-tuner", bench, T=T, b=b, n=n, seed=0)
    adrs_full = adrs(bench.ref_front, res_full.pareto_y)
    if verbose:
        print(f"# Fig4c simplified-model gap ({workload})")
        print(f"  mean relative metric error of simplified model: "
              f"{gap*100:.1f}%")
        print(f"  ADRS of simplified-guided picks (true metrics): "
              f"{adrs_simp:.4f}")
        print(f"  ADRS of full-flow-guided SoC-Tuner:             "
              f"{adrs_full:.4f}")
        print(f"  csv: {path}")
    return {"rel_error": gap, "adrs_simplified": adrs_simp,
            "adrs_full": adrs_full}


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--T", type=int, default=20)
    ap.add_argument("--b", type=int, default=20)
    ap.add_argument("--workload", default="resnet50")
    ap.add_argument("--pool", type=int, default=2500)
    ap.add_argument("--simplified", action="store_true")
    a = ap.parse_args()
    if a.simplified:
        simplified_gap(T=a.T, b=a.b, workload=a.workload, n_pool=a.pool)
    else:
        main(T=a.T, b=a.b, workload=a.workload, n_pool=a.pool)
