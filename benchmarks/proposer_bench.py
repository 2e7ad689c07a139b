"""A/B benchmark: between-round proposer on vs off at EQUAL eval budget.

Both arms run ``soc_tuner`` with ``incremental=True`` on the same pool,
seed and round budget — the proposer does not buy extra flow evaluations,
it only rewrites un-evaluated pool columns between rounds (perturbations
of the current Pareto front, snapped to the design lattice). The question
the benchmark answers is whether that pool refresh finds better designs
for the SAME number of flow calls: per (workload × seed) cell it records
final ADRS for both arms, the gap, the evaluation counts (asserted
identical), and the proposer's own counters (proposed / replaced / wall)
into ``BENCH_proposer.json``::

    PYTHONPATH=src python -m benchmarks.proposer_bench \\
        --workloads resnet50,transformer --seeds 2

``--smoke`` shrinks the protocol (one workload, one seed, tiny pool and
budget) to a <2 min CI gate that exercises the full wiring end-to-end and
still asserts the equal-budget invariant::

    PYTHONPATH=src python -m benchmarks.proposer_bench --smoke
"""
from __future__ import annotations

import argparse
import json
import os
import time

import jax
import numpy as np

from .common import OUT_DIR, make_bench

from repro.core import soc_tuner


def _run_cell(bench, *, seed: int, T: int, n: int, b: int,
              proposer: dict | None, use_kernels: bool = False):
    key = jax.random.PRNGKey(seed)
    flow = bench.flow_factory()
    t0 = time.perf_counter()
    res = soc_tuner(bench.space, bench.pool, flow, T=T, n=n, b=b,
                    reference_front=bench.ref_front, key=key,
                    incremental=True, proposer=proposer,
                    use_kernels=use_kernels)
    wall = time.perf_counter() - t0
    return {
        "final_adrs": float(res.history[-1]["adrs"]),
        "n_evals": int(len(res.evaluated_rows)),
        "front_size": int(len(res.pareto_rows)),
        "wall_s": wall,
        "proposer": (res.engine_stats or {}).get("proposer"),
    }


def run(workloads: list[str], *, seeds: int, n_pool: int, T: int, n: int,
        b: int, every: int, n_propose: int, scale: float, out: str,
        use_kernels: bool = False, smoke: bool = False) -> dict:
    prop = {"enabled": True, "every": every, "n_propose": n_propose,
            "scale": scale}
    cells = []
    for wl in workloads:
        bench = make_bench(wl, n_pool=n_pool, seed=0)
        for s in range(seeds):
            off = _run_cell(bench, seed=s, T=T, n=n, b=b, proposer=None,
                            use_kernels=use_kernels)
            on = _run_cell(bench, seed=s, T=T, n=n, b=b, proposer=prop,
                           use_kernels=use_kernels)
            if on["n_evals"] != off["n_evals"]:
                raise AssertionError(
                    f"unequal eval budget: proposer-on ran {on['n_evals']} "
                    f"flow evals vs {off['n_evals']} off — the arms are "
                    "not comparable")
            cell = {
                "workload": wl, "seed": s,
                "n_evals": off["n_evals"],
                "adrs_off": off["final_adrs"],
                "adrs_on": on["final_adrs"],
                "adrs_gap": on["final_adrs"] - off["final_adrs"],
                "wall_off_s": off["wall_s"], "wall_on_s": on["wall_s"],
                "proposer": on["proposer"],
            }
            cells.append(cell)
            print(f"[proposer_bench] {wl} seed {s}: adrs off "
                  f"{cell['adrs_off']:.4f} vs on {cell['adrs_on']:.4f} "
                  f"(gap {cell['adrs_gap']:+.4f}), "
                  f"{cell['proposer']['replaced']} columns replaced over "
                  f"{cell['proposer']['rounds']} proposal rounds")
    gaps = np.asarray([c["adrs_gap"] for c in cells])
    result = {
        "protocol": {
            "workloads": workloads, "seeds": seeds, "n_pool": n_pool,
            "T": T, "n": n, "b": b, "proposer": prop, "smoke": smoke,
        },
        "cells": cells,
        "summary": {
            "mean_adrs_off": float(np.mean([c["adrs_off"] for c in cells])),
            "mean_adrs_on": float(np.mean([c["adrs_on"] for c in cells])),
            "mean_adrs_gap": float(gaps.mean()),
            "max_adrs_gap": float(gaps.max()),
            "cells_improved": int((gaps < 0).sum()),
            "cells_total": len(cells),
        },
    }
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as f:
        json.dump(result, f, indent=2)
    s = result["summary"]
    print(f"[proposer_bench] mean adrs off {s['mean_adrs_off']:.4f} vs on "
          f"{s['mean_adrs_on']:.4f} (gap {s['mean_adrs_gap']:+.4f}); "
          f"{s['cells_improved']}/{s['cells_total']} cells improved "
          f"-> {out}")
    return result


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workloads", default="resnet50,transformer")
    p.add_argument("--seeds", type=int, default=2)
    p.add_argument("--n-pool", type=int, default=2500)
    p.add_argument("--T", type=int, default=20)
    p.add_argument("--n", type=int, default=40)
    p.add_argument("--b", type=int, default=8)
    p.add_argument("--proposer-every", type=int, default=2)
    p.add_argument("--proposer-n", type=int, default=4)
    p.add_argument("--proposer-scale", type=float, default=0.15)
    p.add_argument("--use-kernels", action="store_true")
    p.add_argument("--smoke", action="store_true",
                   help="tiny single-cell run for CI (wiring + equal-budget "
                        "gate, not a statistically meaningful A/B)")
    p.add_argument("--out",
                   default=os.path.join(OUT_DIR, "BENCH_proposer.json"))
    a = p.parse_args()
    if a.smoke:
        run(["resnet50"], seeds=1, n_pool=96, T=4, n=10, b=6,
            every=1, n_propose=3, scale=0.3,
            out=os.path.join(OUT_DIR, "BENCH_proposer_smoke.json"),
            use_kernels=a.use_kernels, smoke=True)
        return
    run([w for w in a.workloads.split(",") if w], seeds=a.seeds,
        n_pool=a.n_pool, T=a.T, n=a.n, b=a.b, every=a.proposer_every,
        n_propose=a.proposer_n, scale=a.proposer_scale, out=a.out,
        use_kernels=a.use_kernels)


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
