"""Service concurrency benchmark: q-batch workers vs the sequential round.

The exploration service exists because the real VLSI flow costs hours per
design point; this benchmark reproduces that regime with ``DelayedFlow`` (a
fixed per-call sleep on top of the surrogate) and measures the wall-clock
effect of running q concurrent mock-flow workers against the one-at-a-time
baseline at the SAME evaluation budget::

    PYTHONPATH=src python -m benchmarks.service_bench \\
        --n-pool 1024 --T 40 --delay 3.0 --qs 1,4

Emits ``results/benchmarks/BENCH_service.json``: per-q wall/BO-phase wall,
engine + pool stats, and the speedup of each q against q=1 (the ISSUE 4
acceptance gate is >= 3x at q=4, T=40, n_pool=1024). ``T`` counts BO-phase
flow evaluations for every q — see ``repro.service.runner``.

``--fleet`` runs the ISSUE 5 pair instead and emits
``BENCH_fleet_service.json``:

1. **single-scenario async vs barrier** at q=4 workers — post-freeze-y*
   the fully-async ``min_done=1`` mode must meet or beat the per-round
   barrier (``min_done=q``) at the same budget;
2. **fleet-async vs synchronous fleet_tuner** — two scenarios driven by
   ``fleet_service`` over one shared 4-worker pool against ``fleet_tuner``
   paying the same mock flow latency synchronously (via its
   ``flow_factory`` seam).
"""
from __future__ import annotations

import argparse
import json
import os
import time

import jax

from .common import OUT_DIR, make_bench
from repro.soc import DelayedFlow


def run_point(a, q: int) -> dict:
    from repro.service import service_tuner

    bench = make_bench(a.workload, n_pool=a.n_pool, seed=a.seed,
                       with_ref=False)
    flow = DelayedFlow(bench.flow_factory(), a.delay)
    t0 = time.time()
    res = service_tuner(
        bench.space, bench.pool, flow, workload=a.workload, T=a.T, q=q,
        min_done=a.min_done if q > 1 else 1, executor=a.executor,
        max_workers=q, n=a.n, b=a.b, gp_steps=a.gp_steps,
        key=jax.random.PRNGKey(a.seed), bucket=a.bucket,
        fantasy=a.fantasy)
    wall = time.time() - t0
    walls = [h["wall_s"] for h in res.history[1:]]
    stats = dict(res.engine_stats)
    service = stats.pop("service")
    return {
        "q": q,
        "wall_s": wall,
        "bo_wall_s": float(sum(walls)),
        "evaluations": int(len(res.evaluated_rows)),
        "bo_evaluations": a.T,
        **stats,
        "pool": service,
    }


def _single_point(a, min_done: int) -> dict:
    """One single-scenario service run at q workers (async or barrier)."""
    from repro.service import service_tuner

    bench = make_bench(a.workload, n_pool=a.n_pool, seed=a.seed,
                       with_ref=False)
    flow = DelayedFlow(bench.flow_factory(), a.delay)
    t0 = time.time()
    res = service_tuner(
        bench.space, bench.pool, flow, workload=a.workload, T=a.T,
        q=a.fleet_q_total, min_done=min_done, executor=a.executor,
        max_workers=a.fleet_q_total, n=a.n, b=a.b, gp_steps=a.gp_steps,
        key=jax.random.PRNGKey(a.seed), bucket=a.bucket, fantasy=a.fantasy)
    wall = time.time() - t0
    stats = dict(res.engine_stats)
    service = stats.pop("service")
    return {"mode": f"single-q{a.fleet_q_total}-min_done{min_done}",
            "min_done": min_done, "wall_s": wall,
            "bo_wall_s": float(sum(h["wall_s"] for h in res.history[1:])),
            "evaluations": int(len(res.evaluated_rows)),
            "bo_evaluations": a.T, **stats, "pool": service}


def _fleet_scenarios(a):
    from repro.core import FleetScenario

    return [FleetScenario(wl.strip(), seed=int(s))
            for wl in a.fleet_workloads.split(",")
            for s in a.fleet_seeds.split(",")]


def _fleet_sync_point(a) -> dict:
    """Synchronous baseline: fleet_tuner paying the mock latency per flush."""
    from repro.core import fleet_tuner
    from repro.soc import VLSIFlow

    bench = make_bench(a.workload, n_pool=a.n_pool, seed=a.seed,
                       with_ref=False)
    delay = a.delay
    factory = lambda wl: DelayedFlow(VLSIFlow(bench.space, wl), delay)
    scenarios = _fleet_scenarios(a)
    t0 = time.time()
    fr = fleet_tuner(bench.space, bench.pool, scenarios, T=a.fleet_T,
                     n=a.n, b=a.b, gp_steps=a.gp_steps, incremental=True,
                     flow_factory=factory)
    wall = time.time() - t0
    return {"mode": "fleet-sync", "scenarios": [sc.label for sc in scenarios],
            "wall_s": wall,
            "evaluations": int(sum(len(r.evaluated_rows)
                                   for r in fr.results)),
            "bo_evaluations": a.fleet_T * len(scenarios),
            "flow_calls": fr.cache.flow_calls}


def _fleet_async_point(a) -> dict:
    """fleet_service: all scenarios over ONE shared worker pool, min_done=1."""
    from repro.service import fleet_service
    from repro.soc import VLSIFlow

    bench = make_bench(a.workload, n_pool=a.n_pool, seed=a.seed,
                       with_ref=False)
    delay = a.delay
    factory = lambda wl: DelayedFlow(VLSIFlow(bench.space, wl), delay)
    scenarios = _fleet_scenarios(a)
    q = max(1, a.fleet_q_total // len(scenarios))
    t0 = time.time()
    fr = fleet_service(bench.space, bench.pool, scenarios, T=a.fleet_T,
                       q=q, min_done=1, executor=a.executor,
                       max_workers=a.fleet_q_total, n=a.n, b=a.b,
                       gp_steps=a.gp_steps, bucket=a.bucket,
                       fantasy=a.fantasy, flow_factory=factory)
    wall = time.time() - t0
    stats = dict(fr.results[0].engine_stats)
    service = stats.pop("service")
    return {"mode": "fleet-async",
            "scenarios": [sc.label for sc in scenarios],
            "q_per_scenario": q, "workers": a.fleet_q_total,
            "wall_s": wall,
            "evaluations": int(sum(len(r.evaluated_rows)
                                   for r in fr.results)),
            "bo_evaluations": a.fleet_T * len(scenarios),
            **stats, "pool": service}


def fleet_main(a) -> None:
    print(f"[fleet-bench] single-scenario barrier (q={a.fleet_q_total}, "
          f"min_done={a.fleet_q_total}) ...")
    barrier = _single_point(a, a.fleet_q_total)
    print(f"[fleet-bench]   wall {barrier['wall_s']:.1f}s")
    print(f"[fleet-bench] single-scenario async (min_done=1) ...")
    async_ = _single_point(a, 1)
    print(f"[fleet-bench]   wall {async_['wall_s']:.1f}s")
    print(f"[fleet-bench] synchronous fleet_tuner baseline ...")
    sync = _fleet_sync_point(a)
    print(f"[fleet-bench]   wall {sync['wall_s']:.1f}s")
    print(f"[fleet-bench] fleet_service async ...")
    fasync = _fleet_async_point(a)
    print(f"[fleet-bench]   wall {fasync['wall_s']:.1f}s")

    out = {
        "config": {"workload": a.workload, "n_pool": a.n_pool, "T": a.T,
                   "fleet_T": a.fleet_T, "delay_s": a.delay,
                   "executor": a.executor, "fantasy": a.fantasy,
                   "n": a.n, "b": a.b, "gp_steps": a.gp_steps,
                   "bucket": a.bucket, "seed": a.seed,
                   "workers": a.fleet_q_total,
                   "fleet_workloads": a.fleet_workloads,
                   "fleet_seeds": a.fleet_seeds,
                   "backend": jax.default_backend()},
        "points": [barrier, async_, sync, fasync],
        "async_vs_barrier_wall": barrier["wall_s"] / async_["wall_s"],
        "fleet_async_vs_sync_wall": sync["wall_s"] / fasync["wall_s"],
    }
    path = a.out
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    with open(path, "w") as f:
        json.dump(out, f, indent=2)
    print(f"[fleet-bench] min_done=1 vs barrier: "
          f"{out['async_vs_barrier_wall']:.2f}x wall "
          f"(>= 1.0 is the freeze-y* acceptance gate)")
    print(f"[fleet-bench] fleet-async vs sync fleet_tuner: "
          f"{out['fleet_async_vs_sync_wall']:.2f}x wall")
    print(f"[fleet-bench] -> {path}")


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--workload", default="resnet50")
    p.add_argument("--n-pool", type=int, default=1024)
    p.add_argument("--T", type=int, default=40,
                   help="BO-phase evaluation budget (same for every q)")
    p.add_argument("--qs", default="1,4",
                   help="comma-separated q values; q=1 is the baseline")
    p.add_argument("--delay", type=float, default=3.0,
                   help="mock flow latency per call, seconds")
    p.add_argument("--min-done", type=int, default=1,
                   help="completions per refill for q>1 (1 = fully async)")
    p.add_argument("--executor", default="process",
                   choices=("process", "thread", "inline"))
    p.add_argument("--fantasy", default="mean")
    p.add_argument("--n", type=int, default=30)
    p.add_argument("--b", type=int, default=20)
    p.add_argument("--gp-steps", type=int, default=150)
    p.add_argument("--bucket", type=int, default=256,
                   help="engine pad bucket (one jit shape for the whole run)")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--fleet", action="store_true",
                   help="run the ISSUE 5 fleet/async-vs-barrier pair and "
                        "emit BENCH_fleet_service.json instead")
    p.add_argument("--fleet-T", type=int, default=24,
                   help="per-scenario BO budget of the fleet pair")
    p.add_argument("--fleet-workloads", default="resnet50,transformer")
    p.add_argument("--fleet-seeds", default="0")
    p.add_argument("--fleet-q-total", type=int, default=4,
                   help="shared worker count (per-scenario q = total / S)")
    p.add_argument("--out", default=None)
    a = p.parse_args()
    if a.out is None:
        a.out = os.path.join(OUT_DIR, "BENCH_fleet_service.json" if a.fleet
                             else "BENCH_service.json")
    if a.fleet:
        fleet_main(a)
        return

    qs = [int(x) for x in a.qs.split(",")]
    points = []
    for q in qs:
        print(f"[service-bench] q={q} T={a.T} delay={a.delay}s "
              f"({a.executor} executor) ...")
        rec = run_point(a, q)
        points.append(rec)
        print(f"[service-bench]   wall {rec['wall_s']:.1f}s "
              f"(BO phase {rec['bo_wall_s']:.1f}s), "
              f"{rec['pool']['pool_dispatched']} dispatches, "
              f"{rec['fantasy_steps']} fantasy steps")

    base = next((r for r in points if r["q"] == 1), points[0])
    out = {
        "config": {"workload": a.workload, "n_pool": a.n_pool, "T": a.T,
                   "delay_s": a.delay, "min_done": a.min_done,
                   "executor": a.executor, "fantasy": a.fantasy, "n": a.n,
                   "b": a.b, "gp_steps": a.gp_steps, "bucket": a.bucket,
                   "seed": a.seed, "backend": jax.default_backend()},
        "points": points,
        "speedup_wall": {str(r["q"]): base["wall_s"] / r["wall_s"]
                         for r in points},
        "speedup_bo_wall": {str(r["q"]): base["bo_wall_s"] / r["bo_wall_s"]
                            for r in points},
    }
    os.makedirs(os.path.dirname(os.path.abspath(a.out)), exist_ok=True)
    with open(a.out, "w") as f:
        json.dump(out, f, indent=2)
    for r in points:
        if r["q"] != base["q"]:
            print(f"[service-bench] q={r['q']}: "
                  f"{out['speedup_wall'][str(r['q'])]:.2f}x wall speedup "
                  f"vs q=1")
    print(f"[service-bench] -> {a.out}")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
