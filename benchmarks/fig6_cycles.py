"""Fig. 6 — inference latency of each method's chosen optimal SoC across DNN
workloads (ResNet-50 / MobileNet / Transformer — plus LM-arch decode bonus).

Each method explores on ResNet-50 (the paper's protocol), picks its
balanced optimum, and that single SoC design is then evaluated on every
workload.
"""
from __future__ import annotations

import argparse

import numpy as np

from repro.soc import VLSIFlow
from .common import METHODS, make_bench, run_method, write_csv

WORKLOADS = ("resnet50", "mobilenet", "transformer")
BONUS = ("qwen3-14b:decode", "mamba2-370m:decode")


def pick_balanced(res, pool, space):
    front = res.pareto_y
    z = (front - front.min(0)) / np.maximum(np.ptp(front, 0), 1e-12)
    i = int(np.argmin(np.linalg.norm(z, axis=1)))
    return res.pareto_idx(pool)[i]


def main(T: int = 20, b: int = 20, n: int = 30, n_pool: int = 2500,
         methods=METHODS, bonus: bool = True, verbose: bool = True):
    bench = make_bench("resnet50", n_pool=n_pool)
    wls = WORKLOADS + (BONUS if bonus else ())
    rows = []
    for m in methods:
        res = run_method(m, bench, T=T, b=b, n=n, seed=0)
        design = pick_balanced(res, bench.pool, bench.space)
        lat = []
        for w in wls:
            y = np.asarray(VLSIFlow(bench.space, w)(design[None, :]))[0]
            lat.append(float(y[0]))
            rows.append([m, w, round(float(y[0]), 4), round(float(y[1]), 2),
                         round(float(y[2]), 3)])
        if verbose:
            print(f"  {m:<12s} " + "  ".join(
                f"{w.split(':')[0][:9]}={v:8.3f}ms" for w, v in zip(wls, lat)))
    path = write_csv("fig6_cycles.csv",
                     ["method", "workload", "latency_ms", "power_mw",
                      "area_mm2"], rows)
    if verbose:
        print(f"  csv: {path}")
    return rows


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--T", type=int, default=20)
    ap.add_argument("--b", type=int, default=20)
    ap.add_argument("--pool", type=int, default=2500)
    a = ap.parse_args()
    main(T=a.T, b=a.b, n_pool=a.pool)
