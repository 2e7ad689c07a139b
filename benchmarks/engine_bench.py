"""Microbenchmark: incremental BOEngine vs the from-scratch per-round path,
plus the large-pool scaling sweep.

**Engine comparison** (small/medium pools): runs ``soc_tuner`` twice on the
same pool/seed — once with ``incremental=False`` (the historical round: cold
150-step Adam fit, full O(n³) Cholesky, host-side masking/argmax) and once
with ``incremental=True`` (warm-started fits, rank-k Cholesky block updates,
cached pool covariances, device-side selection) — and reports per-round wall
time, dispatch counts, refactor/update mix, final ADRS, and the cross-ADRS
between the two learned Pareto fronts. Results land in ``BENCH_engine.json``::

    PYTHONPATH=src python -m benchmarks.engine_bench --n-pool 1024 --T 40

**Pool scaling** (the 10⁵–10⁶ regime, see docs/scaling.md): a single large
``--n-pool`` — or a ``--pool-sweep`` list — runs the chunked incremental
engine only (no reference front: the pool's O(N²) dominance pass and full
evaluation are neither affordable nor needed) and emits per-round latency +
peak RSS per pool size into ``BENCH_pool.json``. Sweep points run in
subprocesses so each size reports its own honest peak memory::

    PYTHONPATH=src python -m benchmarks.engine_bench --n-pool 100000
    PYTHONPATH=src python -m benchmarks.engine_bench \\
        --pool-sweep 2500,10000,40000,100000

Pool mode engages automatically at ``--n-pool`` >= 20000 (force it lower
with ``--pool-bench``).

**ADRS parity soak** (the evidence gate for flipping ``incremental=True`` to
the default — ROADMAP): ``--soak wl1,wl2,...`` runs exact AND incremental
end-to-end for every (workload × seed) cell, records final ADRS per path,
the gap, and the symmetric front cross-ADRS into ``BENCH_soak.json``::

    PYTHONPATH=src python -m benchmarks.engine_bench \\
        --soak resnet50,mobilenet,transformer --soak-seeds 3 --n-pool 400
"""
from __future__ import annotations

import argparse
import json
import os
import resource
import subprocess
import sys
import tempfile
import time

import jax
import numpy as np

from .common import OUT_DIR, make_bench
from repro.core import adrs, soc_tuner

#: --n-pool at or above this switches to pool-scaling mode by itself.
POOL_MODE_MIN = 20_000


def _run(bench, *, T, n, b, gp_steps, seed, incremental, warm_steps,
         drift_tol, pool_chunk=None):
    flow = bench.flow_factory()
    t0 = time.time()
    res = soc_tuner(bench.space, bench.pool, flow, T=T, n=n, b=b,
                    gp_steps=gp_steps, key=jax.random.PRNGKey(seed),
                    reference_front=bench.ref_front, incremental=incremental,
                    warm_steps=warm_steps, drift_tol=drift_tol,
                    pool_chunk=pool_chunk)
    wall = time.time() - t0
    # round 0 is setup (ICD + TED init); rounds 1..2 pay jit compiles
    walls = np.asarray([h["wall_s"] for h in res.history[1:]])
    out = {
        "wall_s": wall,
        "round_wall_mean_s": float(walls.mean()),
        "round_wall_median_s": float(np.median(walls)),
        "round_wall_steady_s": float(np.median(walls[len(walls) // 2:])),
        "evaluations": int(len(res.evaluated_rows)),
        **res.engine_stats,
    }
    if bench.ref_front is not None:
        out["final_adrs"] = float(res.history[-1]["adrs"])
    return res, out


def _peak_rss_mb() -> float:
    peak = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
    # ru_maxrss is kilobytes on Linux but bytes on macOS
    return peak / (1 << 20) if sys.platform == "darwin" else peak / 1024.0


def pool_point(a) -> dict:
    """One pool-scaling measurement in THIS process (chunked incremental
    engine, no reference front)."""
    chunk = a.pool_chunk
    if chunk not in (None, "auto"):
        chunk = int(chunk)
    bench = make_bench(a.workload, n_pool=a.n_pool, seed=a.seed,
                       with_ref=False)
    _, rec = _run(bench, T=a.T, n=a.n, b=a.b, gp_steps=a.gp_steps,
                  seed=a.seed, incremental=True, warm_steps=a.warm_steps,
                  drift_tol=a.drift_tol, pool_chunk=chunk)
    # points are self-describing: a later single-point run may merge into an
    # existing sweep file, so each point carries its own full configuration
    rec.update(n_pool=a.n_pool, pool_chunk=a.pool_chunk,
               workload=a.workload, T=a.T, n=a.n, b=a.b,
               gp_steps=a.gp_steps, warm_steps=a.warm_steps,
               drift_tol=a.drift_tol, seed=a.seed,
               peak_rss_mb=_peak_rss_mb(), backend=jax.default_backend())
    return rec


def _run_pool_subprocess(a, n_pool: int) -> dict:
    """Run one sweep point isolated so its peak RSS is its own.

    The sweeping parent never starts a JAX backend (it only launches and
    reads children), so each child can open the accelerator in turn."""
    with tempfile.NamedTemporaryFile(suffix=".json", delete=False) as f:
        tmp = f.name
    cmd = [sys.executable, "-m", "benchmarks.engine_bench",
           "--n-pool", str(n_pool), "--pool-bench", "--point-out", tmp,
           "--workload", a.workload, "--T", str(a.T), "--n", str(a.n),
           "--b", str(a.b), "--gp-steps", str(a.gp_steps),
           "--drift-tol", str(a.drift_tol), "--seed", str(a.seed),
           # a.pool_chunk is already normalized ("none" -> None); re-encode
           # it in CLI vocabulary for the child
           "--pool-chunk",
           "none" if a.pool_chunk is None else str(a.pool_chunk)]
    if a.warm_steps is not None:
        cmd += ["--warm-steps", str(a.warm_steps)]
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src")]
        + env.get("PYTHONPATH", "").split(os.pathsep))
    subprocess.run(cmd, check=True, env=env)
    with open(tmp) as f:
        rec = json.load(f)
    os.unlink(tmp)
    return rec


def _pool_main(a) -> None:
    if a.pool_sweep:
        sizes = [int(x) for x in a.pool_sweep.split(",")]
        points = []
        for n_pool in sizes:
            print(f"[engine-bench] pool point n_pool={n_pool} ...")
            rec = _run_pool_subprocess(a, n_pool)
            points.append(rec)
            print(f"[engine-bench]   median round "
                  f"{1e3 * rec['round_wall_median_s']:.0f}ms  "
                  f"peak rss {rec['peak_rss_mb']:.0f}MB")
    else:
        rec = pool_point(a)
        if a.point_out:  # sweep-subprocess mode: emit the point and stop
            with open(a.point_out, "w") as f:
                json.dump(rec, f)
            return
        # merge into an existing sweep file instead of clobbering it
        points = []
        if os.path.exists(a.pool_out):
            try:
                with open(a.pool_out) as f:
                    points = [p for p in json.load(f).get("points", [])
                              if p.get("n_pool") != a.n_pool]
            except (json.JSONDecodeError, OSError):
                points = []
        points = sorted(points + [rec], key=lambda p: p["n_pool"])
        print(f"[engine-bench] n_pool={a.n_pool}  median round "
              f"{1e3 * rec['round_wall_median_s']:.0f}ms  "
              f"peak rss {rec['peak_rss_mb']:.0f}MB  "
              f"({rec['refactors']} refactors / {rec['block_updates']} "
              f"updates)")
    # no top-level config block: points merged across runs carry their own
    out = {"points": points}
    os.makedirs(os.path.dirname(os.path.abspath(a.pool_out)), exist_ok=True)
    with open(a.pool_out, "w") as f:
        json.dump(out, f, indent=2)
    print(f"[engine-bench] {len(points)} pool point(s) -> {a.pool_out}")


def _soak_main(a) -> None:
    """Exact-vs-incremental final-ADRS parity over workloads × seeds."""
    workloads = [w.strip() for w in a.soak.split(",") if w.strip()]
    points = []
    for wl in workloads:
        bench = make_bench(wl, n_pool=a.n_pool, seed=0)  # pool seed pinned
        for seed in range(a.soak_seeds):
            kw = dict(T=a.T, n=a.n, b=a.b, gp_steps=a.gp_steps, seed=seed,
                      warm_steps=a.warm_steps, drift_tol=a.drift_tol)
            res_x, exact = _run(bench, incremental=False, **kw)
            res_i, incr = _run(bench, incremental=True, **kw)
            rec = {
                "workload": wl, "seed": seed,
                "exact_adrs": exact["final_adrs"],
                "incremental_adrs": incr["final_adrs"],
                "adrs_gap": incr["final_adrs"] - exact["final_adrs"],
                "front_cross_adrs": {
                    "exact_ref_vs_incremental": float(adrs(res_x.pareto_y,
                                                           res_i.pareto_y)),
                    "incremental_ref_vs_exact": float(adrs(res_i.pareto_y,
                                                           res_x.pareto_y)),
                },
                "speedup_wall": exact["wall_s"] / incr["wall_s"],
                "refactors": incr["refactors"],
                "block_updates": incr["block_updates"],
            }
            points.append(rec)
            print(f"[engine-bench] soak {wl} seed {seed}: "
                  f"adrs exact {rec['exact_adrs']:.4f} vs incr "
                  f"{rec['incremental_adrs']:.4f} (gap "
                  f"{rec['adrs_gap']:+.4f}), {rec['speedup_wall']:.1f}x wall")
    gaps = np.asarray([r["adrs_gap"] for r in points])
    out = {
        "config": {"workloads": workloads, "seeds": a.soak_seeds,
                   "n_pool": a.n_pool, "T": a.T, "n": a.n, "b": a.b,
                   "gp_steps": a.gp_steps, "warm_steps": a.warm_steps,
                   "drift_tol": a.drift_tol,
                   "backend": jax.default_backend()},
        "points": points,
        "summary": {
            "cells": len(points),
            "mean_adrs_gap": float(gaps.mean()),
            "max_adrs_gap": float(gaps.max()),
            # "not worse": ties count for the incremental path (identical
            # fronts give an exact 0.0 gap)
            "incremental_not_worse": int((gaps <= 0).sum()),
            "mean_speedup_wall": float(np.mean(
                [r["speedup_wall"] for r in points])),
        },
    }
    os.makedirs(os.path.dirname(os.path.abspath(a.soak_out)), exist_ok=True)
    with open(a.soak_out, "w") as f:
        json.dump(out, f, indent=2)
    s = out["summary"]
    print(f"[engine-bench] soak: {s['cells']} cells, mean ADRS gap "
          f"{s['mean_adrs_gap']:+.4f} (max {s['max_adrs_gap']:+.4f}), "
          f"incremental not-worse in "
          f"{s['incremental_not_worse']}/{s['cells']}, "
          f"mean {s['mean_speedup_wall']:.1f}x wall -> {a.soak_out}")


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--workload", default="resnet50")
    p.add_argument("--n-pool", type=int, default=1024)
    p.add_argument("--T", type=int, default=40)
    p.add_argument("--n", type=int, default=30)
    p.add_argument("--b", type=int, default=20)
    p.add_argument("--gp-steps", type=int, default=150)
    p.add_argument("--warm-steps", type=int, default=None)
    p.add_argument("--drift-tol", type=float, default=1.0)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--out", default=os.path.join(OUT_DIR, "BENCH_engine.json"))
    p.add_argument("--pool-bench", action="store_true",
                   help="force pool-scaling mode (auto at --n-pool >= "
                        f"{POOL_MODE_MIN})")
    p.add_argument("--pool-sweep", default=None,
                   help="comma-separated pool sizes, e.g. 2500,10000,100000 "
                        "(each runs in a subprocess for honest peak RSS)")
    p.add_argument("--pool-chunk", default="auto",
                   help="engine pool_chunk in pool mode: 'auto', 'none', or "
                        "an int")
    p.add_argument("--pool-out",
                   default=os.path.join(OUT_DIR, "BENCH_pool.json"))
    p.add_argument("--point-out", default=None, help=argparse.SUPPRESS)
    p.add_argument("--soak", default=None,
                   help="comma-separated workloads: run the exact-vs-"
                        "incremental ADRS parity soak over --soak-seeds "
                        "seeds each")
    p.add_argument("--soak-seeds", type=int, default=3)
    p.add_argument("--soak-out",
                   default=os.path.join(OUT_DIR, "BENCH_soak.json"))
    a = p.parse_args()
    if a.pool_chunk == "none":
        a.pool_chunk = None
    elif a.pool_chunk != "auto":
        a.pool_chunk = int(a.pool_chunk)

    if a.soak:
        _soak_main(a)
        return
    if a.pool_sweep or a.pool_bench or a.n_pool >= POOL_MODE_MIN:
        _pool_main(a)
        return

    bench = make_bench(a.workload, n_pool=a.n_pool, seed=a.seed)
    kw = dict(T=a.T, n=a.n, b=a.b, gp_steps=a.gp_steps, seed=a.seed,
              warm_steps=a.warm_steps, drift_tol=a.drift_tol)
    print(f"[engine-bench] exact path: T={a.T} n_pool={a.n_pool} ...")
    res_x, exact = _run(bench, incremental=False, **kw)
    print(f"[engine-bench]   wall {exact['wall_s']:.1f}s  "
          f"median round {1e3 * exact['round_wall_median_s']:.0f}ms  "
          f"adrs {exact['final_adrs']:.4f}")
    print("[engine-bench] incremental path ...")
    res_i, incr = _run(bench, incremental=True, **kw)
    print(f"[engine-bench]   wall {incr['wall_s']:.1f}s  "
          f"median round {1e3 * incr['round_wall_median_s']:.0f}ms  "
          f"adrs {incr['final_adrs']:.4f}  "
          f"({incr['refactors']} refactors / {incr['block_updates']} updates)")

    out = {
        "config": {"workload": a.workload, "n_pool": a.n_pool, "T": a.T,
                   "n": a.n, "b": a.b, "gp_steps": a.gp_steps,
                   "warm_steps": a.warm_steps, "drift_tol": a.drift_tol,
                   "seed": a.seed, "backend": jax.default_backend()},
        "exact": exact,
        "incremental": incr,
        "speedup_wall": exact["wall_s"] / incr["wall_s"],
        "speedup_round_median": (exact["round_wall_median_s"]
                                 / incr["round_wall_median_s"]),
        # symmetric front agreement: each front scored against the other as
        # reference (0 == identical fronts)
        "front_cross_adrs": {
            "exact_ref_vs_incremental": float(adrs(res_x.pareto_y,
                                                   res_i.pareto_y)),
            "incremental_ref_vs_exact": float(adrs(res_i.pareto_y,
                                                   res_x.pareto_y)),
        },
    }
    os.makedirs(os.path.dirname(os.path.abspath(a.out)), exist_ok=True)
    with open(a.out, "w") as f:
        json.dump(out, f, indent=2)
    print(f"[engine-bench] speedup {out['speedup_wall']:.2f}x wall, "
          f"{out['speedup_round_median']:.2f}x median round -> {a.out}")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
