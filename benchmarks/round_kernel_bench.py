"""A/B microbenchmark of the fused acquisition-round kernel.

Times ``kernels.backend.round_score_auto`` — one round's scoring half
(trailing V update, posterior moments, MES, masking, global argmax) — on a
synthetic engine-shaped problem at several pool sizes, once through the
staged XLA route (``backend="xla"``, the fidelity default the golden
trajectories pin) and once through the fused Pallas route
(``backend="pallas"``), asserting the two select the identical candidate at
every size. Also records the per-stage round breakdown from a short
profiled engine run (``BOEngine(profile_stages=True)``). Results land in
``BENCH_round_kernel.json``::

    PYTHONPATH=src python -m benchmarks.round_kernel_bench
    PYTHONPATH=src python -m benchmarks.round_kernel_bench --smoke

Off-TPU the Pallas route runs under ``interpret=True`` (recorded in the
output): correctness is meaningful there, the timing is not — the fused
numbers only represent hardware when ``backend == "tpu"``.
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from .common import OUT_DIR
from repro.core import BOEngine
from repro.core.engine import PROFILE_STAGES
from repro.core.gp import GPParams
from repro.kernels.backend import round_score_auto
from repro.kernels.common import cdiv, use_interpret

#: per-chunk candidate columns for the synthetic pools (the engine's
#: auto_chunk serves the same role in production; a fixed value here keeps
#: the A/B grid shape deterministic across sizes)
CHUNK_C = 12_800


def _problem(n_pool: int, *, P: int = 128, m: int = 3, d: int = 26,
             S: int = 16, s0: int = 0, seed: int = 0) -> dict:
    """One engine-shaped round problem over ``n_pool`` candidates, chunked
    into ``[nc, C]`` columns with the tail padded and masked evaluated."""
    C = min(n_pool, CHUNK_C)
    nc = cdiv(n_pool, C)
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    params = GPParams(log_ls=0.3 * jax.random.normal(ks[0], (m, d)),
                      log_var=0.2 * jax.random.normal(ks[1], (m,)),
                      log_noise=jnp.full((m,), -4.0))
    x = jax.random.normal(ks[2], (P, d))
    pool = jax.random.normal(ks[3], (nc * C, d))
    pool_c = pool.reshape(nc, C, d)
    A = jax.random.normal(ks[4], (m, P, P)) / np.sqrt(P)
    L = jnp.linalg.cholesky(A @ jnp.swapaxes(A, -1, -2) + 0.5 * jnp.eye(P))
    beta = jax.random.normal(ks[5], (m, P))
    ystar = jax.random.normal(ks[6], (S, m))
    evalm = jnp.zeros((nc * C,), bool).at[n_pool:].set(True)
    evalm = evalm.at[:3].set(True)
    return dict(params_ref=params, L=L,
                V=jnp.zeros((nc, m, P, C), jnp.float32), x=x, beta=beta,
                ystar=ystar, pool_c=pool_c, evalm_c=evalm.reshape(nc, C),
                base=jnp.arange(nc, dtype=jnp.int32) * C,
                y_mean=jnp.zeros((m,)), y_std=jnp.ones((m,)),
                weights=jnp.ones((m,)) / m), s0


def _time_backend(prob: dict, s0: int, backend: str, reps: int) -> tuple:
    fn = jax.jit(functools.partial(round_score_auto, s0=s0, backend=backend))
    v, idx = fn(**prob)  # compile + first run
    jax.block_until_ready((v, idx))
    walls = []
    for _ in range(reps):
        t0 = time.perf_counter()
        out = fn(**prob)
        jax.block_until_ready(out)
        walls.append(time.perf_counter() - t0)
    return float(np.median(walls)), int(idx)


def _stage_breakdown(n_pool: int, rounds: int, seed: int = 0) -> dict:
    """Per-stage wall shares from a short profiled engine run."""
    rng = np.random.default_rng(seed)
    pool = rng.normal(size=(n_pool, 5)).astype(np.float32)
    W = rng.normal(size=(5, 3))

    def f(rows):
        return np.tanh(pool[np.asarray(rows)] @ W).astype(np.float32)

    eng = BOEngine(pool, incremental=True, gp_steps=25, warm_steps=5,
                   drift_tol=5.0, profile_stages=True)
    init = list(range(12))
    eng.observe(init, f(init))
    key = jax.random.PRNGKey(seed)
    for _ in range(rounds):
        key, k = jax.random.split(key)
        nxt = eng.select(k, sub_rows=np.arange(n_pool, dtype=np.int32))
        eng.observe([nxt], f([nxt]))
    wall = dict(eng.stats.stage_wall_s)
    total = wall["round_total"]
    stage_sum = sum(v for k, v in wall.items() if k != "round_total")
    return {"n_pool": n_pool, "rounds": rounds,
            "stage_wall_s": wall,
            "stage_frac": {k: wall[k] / total for k in PROFILE_STAGES},
            "stage_sum_over_total": stage_sum / total}


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--sizes", default="1000,10000,100000",
                   help="comma-separated pool sizes for the A/B grid")
    p.add_argument("--reps", type=int, default=3)
    p.add_argument("--P", type=int, default=128,
                   help="padded training rows (engine bucket size)")
    p.add_argument("--s0", type=int, default=0,
                   help="reused V rows (0 = full-refactor round, the "
                        "heaviest; P = score-only re-score)")
    p.add_argument("--smoke", action="store_true",
                   help="CI mode: smallest size only, 1 rep, tiny profile")
    p.add_argument("--out",
                   default=os.path.join(OUT_DIR, "BENCH_round_kernel.json"))
    a = p.parse_args()
    sizes = [int(s) for s in a.sizes.split(",")]
    if a.smoke:
        sizes, a.reps = sizes[:1], 1

    interpret = use_interpret()
    points = []
    for n_pool in sizes:
        prob, s0 = _problem(n_pool, P=a.P, s0=a.s0)
        xla_s, xla_idx = _time_backend(prob, s0, "xla", a.reps)
        # interpret-mode fused launches pay a large per-grid-step python
        # dispatch tax — one rep is plenty for the (non-representative)
        # off-TPU timing; the picks-equal check is the real assertion here
        pallas_s, pallas_idx = _time_backend(prob, s0, "pallas",
                                             1 if interpret else a.reps)
        assert pallas_idx == xla_idx, \
            f"pick divergence at n_pool={n_pool}: {pallas_idx} != {xla_idx}"
        rec = {"n_pool": n_pool, "P": a.P, "s0": s0,
               "xla_ms": 1e3 * xla_s, "pallas_ms": 1e3 * pallas_s,
               "speedup_fused": xla_s / pallas_s, "picks_equal": True}
        points.append(rec)
        print(f"[round-bench] n_pool={n_pool:>7}  xla {1e3 * xla_s:9.1f}ms  "
              f"pallas {1e3 * pallas_s:9.1f}ms  "
              f"({rec['speedup_fused']:.2f}x, picks equal)")

    prof = _stage_breakdown(512 if a.smoke else 4096, 2 if a.smoke else 4)
    print(f"[round-bench] stage breakdown @ n_pool={prof['n_pool']}: "
          + "  ".join(f"{k} {100 * prof['stage_frac'][k]:.0f}%"
                      for k in PROFILE_STAGES)
          + f"  (coverage {100 * prof['stage_sum_over_total']:.1f}%)")

    out = {
        "config": {"sizes": sizes, "reps": a.reps, "P": a.P, "s0": a.s0,
                   "backend": jax.default_backend(),
                   "pallas_interpret": interpret, "chunk_c": CHUNK_C},
        "ab_points": points,
        "stage_breakdown": prof,
    }
    os.makedirs(os.path.dirname(os.path.abspath(a.out)), exist_ok=True)
    with open(a.out, "w") as f:
        json.dump(out, f, indent=2)
    print(f"[round-bench] {len(points)} A/B point(s) -> {a.out}")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
