"""Fig. 5 — ICD importance analysis (n=30, v_th=0.07) + pruning ratio.

Paper: "the whole design space points are pruned by about 30.16%". We report
the two defensible readings of that number for our space (the paper does not
define its measure): (a) fraction of candidate *values* removed by pinning,
(b) log10 reduction of the cartesian space. Reading (a) is what lands near
30% at the paper's v_th.
"""
from __future__ import annotations

import argparse

import jax
import numpy as np

from repro.core import icd, make_space
from repro.soc import VLSIFlow
from .common import write_csv


def candidate_removal_fraction(space, pruned) -> float:
    total = sum(f.t for f in space.features)
    removed = sum(space.features[i].t - 1 for i in pruned.pinned)
    return removed / total


def main(n: int = 30, v_th: float = 0.07, workload: str = "resnet50",
         seed: int = 0, verbose: bool = True):
    space = make_space()
    flow = VLSIFlow(space, workload)
    v, idx, y = icd(space, flow, n=n, key=jax.random.PRNGKey(seed))
    pruned = space.prune(v, v_th)
    frac_candidates = candidate_removal_fraction(space, pruned)
    rows = [[f.name, f.group, round(float(v[i]), 5),
             int(i in pruned.pinned)]
            for i, f in enumerate(space.features)]
    rows.sort(key=lambda r: -r[2])
    path = write_csv("fig5_importance.csv",
                     ["feature", "group", "icd_importance", "pinned"], rows)
    # calibrated reading: the v_th that reproduces the paper's ~30.16%
    # candidate removal on OUR flow (our analytic evaluator spreads
    # importance flatter than the paper's VLSI flow, so the absolute
    # threshold is calibration-dependent; the *mechanism* is identical)
    order = np.sort(v)
    v_th_cal, removal_cal = v_th, frac_candidates
    for k in range(1, space.d):
        cand = float((order[k - 1] + order[k]) / 2)
        p2 = space.prune(v, cand)
        r2 = candidate_removal_fraction(space, p2)
        if r2 >= 0.30:
            v_th_cal, removal_cal = cand, r2
            break
    if verbose:
        print(f"# Fig5 ICD importance (n={n}, v_th={v_th}, {workload})")
        for r in rows:
            bar = "#" * int(r[2] * 150)
            print(f"  {r[0]:<10s} {r[2]:.4f} {'PINNED' if r[3] else '':6s} {bar}")
        print(f"  features pinned: {len(pruned.pinned)}/{space.d}")
        print(f"  candidate-value removal @v_th={v_th}: "
              f"{frac_candidates*100:.2f}% (paper reports ~30.16%)")
        print(f"  calibrated v_th={v_th_cal:.4f} -> removal "
              f"{removal_cal*100:.2f}% "
              f"({len(space.prune(v, v_th_cal).pinned)} features pinned)")
        print(f"  log10 |space|: {space.log10_size:.2f} -> "
              f"{pruned.log10_size:.2f}")
        print(f"  csv: {path}")
    return {"pinned": len(pruned.pinned),
            "candidate_removal_pct": frac_candidates * 100,
            "v_th_calibrated": v_th_cal,
            "removal_calibrated_pct": removal_cal * 100,
            "v": v}


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=30)
    ap.add_argument("--v-th", type=float, default=0.07)
    ap.add_argument("--workload", default="resnet50")
    ap.add_argument("--seed", type=int, default=0)
    a = ap.parse_args()
    main(a.n, a.v_th, a.workload, a.seed)
