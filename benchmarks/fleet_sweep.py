"""Fleet sweep — many (workload × seed × weighting) explorations, one process.

The paper's protocol evaluates one workload at a time; real SoC DSE wants an
edge device co-designed against a *portfolio* of networks. This benchmark
runs the whole portfolio through ``repro.core.fleet_tuner``: one vmapped GP
fit + IMOO acquisition per round for every scenario, one shared memoized flow
cache across the fleet, and fused cross-workload evaluation dispatches.

    PYTHONPATH=src python -m benchmarks.fleet_sweep \
        --workloads resnet50,mobilenet,transformer --seeds 2 --T 15 --pool 800

Reports per-scenario final ADRS (vs the pool's true per-workload front),
fleet cache statistics, and the speed-relevant dispatch counts; writes
``results/benchmarks/fleet_sweep.csv``.

Multi-device: ``--mesh`` shards the scenario axis over every visible device
with ``shard_map`` (implies ``--incremental``; the scenario count must
divide the device count). On a CPU-only host, fake a fleet of devices with
XLA's host-platform override — set it BEFORE python starts::

    XLA_FLAGS=--xla_force_host_platform_device_count=2 \
        PYTHONPATH=src python -m benchmarks.fleet_sweep --mesh --seeds 2
"""
from __future__ import annotations

import argparse
import time

from .common import make_bench, run_fleet, write_csv


def parse_weights(spec: str) -> tuple[tuple[float, float, float], ...]:
    """'1,1,1;2,1,1' -> ((1,1,1), (2,1,1)) — one fleet axis per weighting."""
    out = []
    for chunk in spec.split(";"):
        w = tuple(float(x) for x in chunk.split(","))
        assert len(w) == 3, f"weighting needs 3 values, got {chunk!r}"
        out.append(w)
    return tuple(out)


def make_fleet_mesh():
    """One-axis ("fleet",) mesh over every visible device, or None when the
    host only has one (sharding a 1-device mesh is pure overhead)."""
    import jax
    import numpy as np
    from jax.sharding import Mesh

    devs = jax.devices()
    if len(devs) < 2:
        print(f"# fleet sweep: only {len(devs)} device visible — running "
              "unsharded (set XLA_FLAGS=--xla_force_host_platform_"
              "device_count=K before python starts to fake K CPU devices)")
        return None
    return Mesh(np.asarray(devs), ("fleet",))


def main(workloads=("resnet50", "mobilenet", "transformer"), seeds: int = 2,
         T: int = 15, b: int = 12, n: int = 20, n_pool: int = 800,
         weights=((1.0, 1.0, 1.0),), verbose: bool = True,
         incremental: bool = False, mesh: bool = False,
         pool_chunk=None):
    t0 = time.time()
    benches = [make_bench(w, n_pool=n_pool) for w in workloads]
    t_ref = time.time() - t0

    fleet_kw = {}
    if mesh:
        incremental = True  # sharding requires the device-resident engine
        fleet_kw["mesh"] = make_fleet_mesh()
    if incremental:
        fleet_kw["incremental"] = True
    if pool_chunk is not None:
        fleet_kw["pool_chunk"] = pool_chunk
        fleet_kw["incremental"] = True

    t0 = time.time()
    fr = run_fleet(benches, seeds, T=T, b=b, n=n, weights=weights,
                   verbose=False, **fleet_kw)
    t_fleet = time.time() - t0

    rows = []
    for sc, res in zip(fr.scenarios, fr.results):
        final = res.history[-1]
        rows.append([sc.label, sc.workload, sc.seed,
                     "x".join(f"{w:g}" for w in sc.weights),
                     round(final["adrs"], 5), final["evaluations"],
                     final["pareto_size"]])
    path = write_csv("fleet_sweep.csv",
                     ["scenario", "workload", "seed", "weights", "adrs",
                      "evaluations", "pareto_size"], rows)
    write_csv("fleet_sweep_cache.csv",
              ["requests", "hits", "hit_rate", "evaluated", "flow_calls"],
              [[fr.cache.requests, fr.cache.hits,
                round(fr.cache.hit_rate, 4), fr.cache.evaluated,
                fr.cache.flow_calls]])
    if verbose:
        print(f"# fleet sweep: {len(fr.scenarios)} scenarios "
              f"({len(workloads)} workloads x {seeds} seeds x "
              f"{len(weights)} weightings), pool={n_pool}, T={T}")
        for r in rows:
            print(f"  {r[0]:<28s} adrs={r[4]:.4f} evals={r[5]:4d} "
                  f"front={r[6]:3d}")
        print(f"  {fr.cache.summary()}")
        print(f"  wall: {t_fleet:.1f}s fleet ({t_fleet / len(fr.scenarios):.1f}s"
              f"/scenario) + {t_ref:.1f}s reference fronts; csv: {path}")
    return fr


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workloads", default="resnet50,mobilenet,transformer",
                    help="comma-separated workload names (see repro.soc)")
    ap.add_argument("--seeds", type=int, default=2)
    ap.add_argument("--T", type=int, default=15)
    ap.add_argument("--b", type=int, default=12)
    ap.add_argument("--n", type=int, default=20)
    ap.add_argument("--pool", type=int, default=800)
    ap.add_argument("--weights", default="1,1,1",
                    help="';'-separated objective weightings, e.g. '1,1,1;2,1,1'")
    ap.add_argument("--incremental", action="store_true",
                    help="run the fleet on the device-resident incremental "
                         "engine")
    ap.add_argument("--mesh", action="store_true",
                    help="shard the scenario axis over all visible devices "
                         "(implies --incremental)")
    ap.add_argument("--pool-chunk", default=None,
                    help="engine pool_chunk: an int or 'auto' (implies "
                         "--incremental)")
    a = ap.parse_args()
    chunk = a.pool_chunk if a.pool_chunk in (None, "auto") else int(a.pool_chunk)
    main(workloads=tuple(a.workloads.split(",")), seeds=a.seeds, T=a.T,
         b=a.b, n=a.n, n_pool=a.pool, weights=parse_weights(a.weights),
         incremental=a.incremental, mesh=a.mesh, pool_chunk=chunk)
