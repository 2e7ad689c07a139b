"""Shared benchmark scaffolding: the §IV experimental protocol.

Paper protocol: 2500 uniformly sampled design points evaluated with the
(surrogate) VLSI flow form the finite metric space; methods are compared by
ADRS against that pool's true Pareto front, repeated over seeds. Pool
metrics are cached under results/bench_cache/ — evaluation is one batched
XLA call, but the cache keeps repeated figure runs identical and instant.
A cache file is keyed by workload, pool size, seed AND a digest of the
evaluation code, so an edited cost model misses instead of serving stale
metrics.
"""
from __future__ import annotations

import hashlib
import os
from dataclasses import dataclass

import jax
import numpy as np

from repro.core import make_space, pareto_front
from repro.soc import VLSIFlow, SimplifiedFlow

CACHE_DIR = os.path.join(os.path.dirname(__file__), "..", "results",
                         "bench_cache")
OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "results",
                       "benchmarks")

#: the code a cached pool's metrics depend on (paths under src/repro/)
_EVAL_SOURCES = ("soc/model.py", "soc/simplified.py", "soc/workloads.py",
                 "core/space.py")


def _eval_code_digest() -> str:
    import repro

    h = hashlib.sha256()
    for rel in _EVAL_SOURCES:
        with open(os.path.join(list(repro.__path__)[0], rel), "rb") as f:
            h.update(f.read())
    return h.hexdigest()[:12]


METHODS = ("soc-tuner", "microal", "regression", "xgb", "rf", "svr", "random")


@dataclass
class Bench:
    space: object
    pool: np.ndarray          # [N, d] candidate index vectors
    y: np.ndarray | None      # [N, 3] flow metrics for the whole pool
    ref_front: np.ndarray | None  # true Pareto front of the pool
    flow_factory: object      # () -> fresh VLSIFlow (for budget counting)
    workload: str
    simplified: bool = False  # ref/pool came from SimplifiedFlow


def make_bench(workload: str = "resnet50", n_pool: int = 2500,
               seed: int = 0, simplified: bool = False,
               with_ref: bool = True) -> Bench:
    """Build a benchmark pool (+ true Pareto front when ``with_ref``).

    ``with_ref=False`` skips evaluating the whole pool and the O(N²)
    dominance pass — required for the 10⁵–10⁶ pool-scaling benchmarks, where
    the reference front is neither affordable nor needed (they measure
    latency/memory, not ADRS)."""
    os.makedirs(CACHE_DIR, exist_ok=True)
    space = make_space()
    flow_cls = SimplifiedFlow if simplified else VLSIFlow
    if not with_ref:
        pool = np.asarray(space.sample(jax.random.PRNGKey(seed), n_pool))
        return Bench(space=space, pool=pool, y=None, ref_front=None,
                     flow_factory=lambda: flow_cls(space, workload),
                     workload=workload, simplified=simplified)
    tag = (f"{workload}_{n_pool}_{seed}{'_simp' if simplified else ''}"
           f"_{_eval_code_digest()}")
    cache = os.path.join(CACHE_DIR, tag + ".npz")
    if os.path.exists(cache):
        z = np.load(cache)
        pool, y = z["pool"], z["y"]
    else:
        pool = np.asarray(space.sample(jax.random.PRNGKey(seed), n_pool))
        y = np.asarray(flow_cls(space, workload)(pool))
        np.savez(cache, pool=pool, y=y)
    return Bench(space=space, pool=pool, y=y, ref_front=pareto_front(y),
                 flow_factory=lambda: flow_cls(space, workload),
                 workload=workload, simplified=simplified)


def run_method(name: str, bench: Bench, *, T: int, b: int, n: int,
               seed: int = 0, use_kernels: bool = False):
    from repro.core import run_baseline, soc_tuner
    key = jax.random.PRNGKey(seed)
    flow = bench.flow_factory()
    if name == "soc-tuner":
        return soc_tuner(bench.space, bench.pool, flow, T=T, n=n, b=b,
                         reference_front=bench.ref_front, key=key,
                         use_kernels=use_kernels)
    return run_baseline(name, bench.space, bench.pool, flow, T=T, b=b,
                        key=key, reference_front=bench.ref_front)


def run_fleet(benches: "list[Bench]", seeds: int, *, T: int, b: int, n: int,
              weights=((1.0, 1.0, 1.0),), verbose: bool = False,
              **fleet_kw):
    """All (workload × seed × weighting) scenarios in ONE fleet_tuner call.

    Every ``Bench`` must share the same candidate pool (they do when built by
    ``make_bench`` with the same ``n_pool``/``seed`` — the pool draw does not
    depend on the workload). Extra ``fleet_kw`` (``incremental``, ``mesh``,
    ``pool_chunk``, ...) pass straight to :func:`repro.core.fleet_tuner`.
    Returns the ``FleetResult``.
    """
    from repro.core import FleetScenario, fleet_tuner
    for bn in benches:
        if bn.simplified:
            raise ValueError(
                "fleet evaluation always uses the full surrogate; a "
                "simplified bench's reference front would score it "
                "meaninglessly")
        if not np.array_equal(bn.pool, benches[0].pool):
            raise ValueError("fleet scenarios must share one candidate pool")
    scenarios = [FleetScenario(bn.workload, seed=s, weights=tuple(w))
                 for bn in benches for s in range(seeds) for w in weights]
    return fleet_tuner(
        benches[0].space, benches[0].pool, scenarios, T=T, n=n, b=b,
        reference_fronts={bn.workload: bn.ref_front for bn in benches},
        verbose=verbose, **fleet_kw)


def write_csv(name: str, header: list[str], rows: list[list]) -> str:
    os.makedirs(OUT_DIR, exist_ok=True)
    path = os.path.join(OUT_DIR, name)
    with open(path, "w") as f:
        f.write(",".join(header) + "\n")
        for r in rows:
            f.write(",".join(str(x) for x in r) + "\n")
    return path
