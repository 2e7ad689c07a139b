"""Multi-tenant server benchmark: N jobs multiplexed vs run back-to-back.

The ``TunerServer`` exists so tenants don't queue behind each other's
flow latency: while one job waits on its in-flight evaluations, the
scheduler steps the others, and the shared worker pool keeps every worker
busy. This benchmark reproduces the hours-long-flow regime with
``DelayedFlow`` (a fixed per-call sleep) and measures the same set of
jobs twice at the SAME per-job budget:

1. **multiplexed** — all jobs on one ``TunerServer`` over one shared
   worker pool;
2. **sequential** — the same specs one after another through
   ``fleet_service`` (each run still gets the full worker pool — the
   baseline an operator without a job scheduler would run).

Emits ``results/benchmarks/BENCH_server.json``: per-mode wall clock, the
multiplexed speedup, pool statistics, and a per-job bitwise check that
multiplexing did not change any trajectory (the isolation guarantee the
tests pin, visible here at benchmark scale).

Note the overlap needs ``q >= 2``: the parity-exact cycle refills the
in-flight set and then immediately drains ``min_done`` completions, so a
``q=1`` job collects the ticket it just submitted — zero pipeline depth
by construction, in BOTH modes. With ``q=2, min_done=1`` the drained
ticket is a full scheduler round old and its latency hides behind the
other tenants' engine work::

    PYTHONPATH=src python -m benchmarks.server_bench \\
        --n-pool 256 --T 12 --delay 2.0 --workers 6
"""
from __future__ import annotations

import argparse
import json
import os
import time

from .common import OUT_DIR, make_bench
from repro.core import FleetScenario
from repro.soc import DelayedFlow, VLSIFlow


def _specs(a) -> list[dict]:
    pairs = [("resnet50", 0), ("transformer", 1), ("mobilenet", 0),
             ("resnet50", 1), ("transformer", 0), ("mobilenet", 1)]
    return [dict(workload=wl, seed=s, T=a.T, q=a.q, min_done=1,
                 n=a.n, b=a.b, gp_steps=a.gp_steps)
            for wl, s in pairs[:a.jobs]]


def run_multiplexed(a, bench, specs) -> tuple[dict, dict]:
    from repro.service import JobSpec, TunerServer

    factory = lambda wl: DelayedFlow(VLSIFlow(bench.space, wl), a.delay)
    t0 = time.time()
    with TunerServer(bench.space, bench.pool, executor=a.executor,
                     max_workers=a.workers, flow_factory=factory) as srv:
        jids = [srv.submit(JobSpec(**sp)) for sp in specs]
        srv.run_until_idle()
        wall = time.time() - t0
        status = srv.status()
        traj = {}
        for jid, sp in zip(jids, specs):
            job = srv.job(jid)
            assert job.status == "DONE", (jid, job.status, job.error)
            res = job.result()
            traj[_label(sp)] = (list(map(int, res.evaluated_rows)),
                                res.y.tolist())
    return {"wall_s": wall, "pool": status["pool"],
            "total_done": status["total_done"]}, traj


def run_sequential(a, bench, specs) -> tuple[dict, dict]:
    from repro.service import fleet_service

    walls, traj = [], {}
    for sp in specs:
        sc = FleetScenario(sp["workload"], seed=sp["seed"])
        factory = lambda wl: DelayedFlow(VLSIFlow(bench.space, wl), a.delay)
        t0 = time.time()
        fr = fleet_service(
            bench.space, bench.pool, [sc], executor=a.executor,
            max_workers=a.workers, flow_factory=factory,
            **{k: sp[k] for k in ("T", "q", "min_done", "n", "b",
                                  "gp_steps")})
        walls.append(time.time() - t0)
        res = fr.results[0]
        traj[_label(sp)] = (list(map(int, res.evaluated_rows)),
                            res.y.tolist())
    return {"wall_s": float(sum(walls)), "per_job_wall_s": walls}, traj


def _label(sp) -> str:
    return f"{sp['workload']}:s{sp['seed']}"


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--jobs", type=int, default=3,
                   help="number of tenant jobs (distinct workload/seed)")
    p.add_argument("--n-pool", type=int, default=256)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--T", type=int, default=12)
    p.add_argument("--q", type=int, default=2,
                   help="in-flight evaluations per job (overlap needs >= 2)")
    p.add_argument("--delay", type=float, default=2.0,
                   help="mock flow latency per evaluation (seconds)")
    p.add_argument("--workers", type=int, default=6)
    p.add_argument("--executor", default="thread",
                   choices=("process", "thread"))
    p.add_argument("--n", type=int, default=12)
    p.add_argument("--b", type=int, default=8)
    p.add_argument("--gp-steps", type=int, default=30)
    a = p.parse_args()

    bench = make_bench("resnet50", n_pool=a.n_pool, seed=a.seed,
                       with_ref=False)
    specs = _specs(a)
    print(f"[server-bench] {len(specs)} jobs, T={a.T}, q={a.q}, "
          f"delay={a.delay}s, {a.workers} {a.executor} workers")

    # warm the jit cache so neither mode pays the other's compilations
    from repro.service import fleet_service

    fleet_service(bench.space, bench.pool,
                  [FleetScenario(specs[0]["workload"], seed=99)],
                  executor="inline", T=2, q=a.q, min_done=1, n=a.n, b=a.b,
                  gp_steps=a.gp_steps)
    print("[server-bench] jit warmup done")

    mux, mux_traj = run_multiplexed(a, bench, specs)
    print(f"[server-bench] multiplexed: {mux['wall_s']:.1f}s "
          f"(pool {mux['pool']})")
    seq, seq_traj = run_sequential(a, bench, specs)
    print(f"[server-bench] sequential:  {seq['wall_s']:.1f}s")

    identical = {lbl: mux_traj[lbl] == seq_traj[lbl] for lbl in mux_traj}
    assert all(identical.values()), (
        f"multiplexing changed a trajectory: {identical}")
    speedup = seq["wall_s"] / mux["wall_s"]
    print(f"[server-bench] speedup {speedup:.2f}x; all {len(specs)} "
          "trajectories bitwise-identical across modes")

    os.makedirs(OUT_DIR, exist_ok=True)
    out = os.path.join(OUT_DIR, "BENCH_server.json")
    with open(out, "w") as f:
        json.dump({
            "config": {"jobs": len(specs), "n_pool": a.n_pool, "T": a.T,
                       "q": a.q, "delay_s": a.delay, "workers": a.workers,
                       "executor": a.executor, "n": a.n, "b": a.b,
                       "gp_steps": a.gp_steps,
                       "specs": [_label(sp) for sp in specs]},
            "multiplexed": mux,
            "sequential": seq,
            "speedup": speedup,
            "trajectories_identical": identical,
        }, f, indent=2)
    print(f"[server-bench] -> {out}")
    return 0


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    raise SystemExit(main())
