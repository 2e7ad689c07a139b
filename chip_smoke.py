"""Bring-up smoke run of the exploration system on one TPU chip.

Drives the main path once through the entry points a user calls, at the
paper's deployment width (2 500-design pools, n=30 ICD trials, b=20 TED
seeds), with pools and reference fronts made from a fixed seed in the run:

- ``device``   the platform must be ``tpu``; anything else exits non-zero
- ``paper``    ``soc_tuner`` on resnet50, exact and incremental engines
- ``large``    ``soc_tuner`` over 100 000 candidates, ``pool_chunk="auto"``
- ``fleet``    ``fleet_tuner`` over 3 workloads x 2 seeds on one chip
- ``service``  ``soc-service fleet`` in-process with spawned flow workers
- ``kernels``  the four Pallas kernels compiled for the chip vs the XLA path

Every phase prints one JSON line (name, seconds, the checks it made); any
failed check raises and the script exits non-zero. The last line is
``{"ok": true, "device": {...}}`` and is printed only when every phase
passed. ``--four-chips`` runs the device phase and the sharded fleet over
four chips against the same fleet on one chip, and nothing else::

    python chip_smoke.py
    python chip_smoke.py --four-chips
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

SEED = 0
WORKLOADS = ("resnet50", "mobilenet", "transformer")
#: the paper's pool, padded to the 128-row kernel tile for the kernel phase
N_PAPER, N_TILED = 2500, 2560
N_LARGE = 100_000


class SmokeFailure(RuntimeError):
    pass


def _check(ok, what: str) -> str:
    if not ok:
        raise SmokeFailure(what)
    return what


def _emit(phase: str, t0: float, checks: list, **fields) -> None:
    rec = {"phase": phase, "seconds": time.monotonic() - t0, **fields,
           "checks": checks}
    print(json.dumps(rec), flush=True)


def _peak_bytes(dev) -> int | None:
    stats = dev.memory_stats()
    return None if stats is None else stats.get("peak_bytes_in_use")


def _pool(n_pool: int):
    import jax
    import numpy as np
    from repro.core import make_space

    space = make_space()
    return space, np.asarray(space.sample(jax.random.PRNGKey(SEED), n_pool))


def _reference_front(space, pool, workload: str):
    import numpy as np
    from repro.core import pareto_front
    from repro.soc import VLSIFlow

    return pareto_front(np.asarray(VLSIFlow(space, workload)(pool)))


def _check_result(res, label: str, *, T: int | None = None) -> list:
    """Finite positive metrics, no row evaluated twice, and (with a
    reference front) a finite ADRS that does not rise over the rounds."""
    import numpy as np

    rows = np.asarray(res.evaluated_rows)
    y = np.asarray(res.y)
    checks = [
        _check(np.all(np.isfinite(y)) and np.all(y > 0),
               f"{label}: {y.shape[0]} metric triples finite and positive"),
        _check(len(np.unique(rows)) == len(rows),
               f"{label}: no pool row evaluated twice"),
    ]
    if T is not None:
        rounds = len(res.history) - 1
        checks.append(_check(rounds == T, f"{label}: {rounds} rounds run"))
    a = [h["adrs"] for h in res.history if "adrs" in h]
    if a:
        checks.append(_check(
            np.all(np.isfinite(a)) and a[-1] <= a[0],
            f"{label}: ADRS finite, {a[0]:.6g} -> {a[-1]:.6g}"))
    return checks


# ------------------------------------------------------------------ phases
def phase_device() -> dict:
    import jax

    t0 = time.monotonic()
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise SmokeFailure(f"no TPU: jax.devices()[0] is {d.platform!r}")
    info = {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}
    _emit("device", t0, [f"platform is tpu ({d.device_kind})"], **info,
          memory_stats=d.memory_stats())
    return info


def phase_paper(n_pool: int = N_PAPER, T: int = 8, n: int = 30, b: int = 20,
                gp_steps: int = 150) -> None:
    """One scenario at the paper's scale, exact and incremental engines,
    plus the chip's cost model against the CPU's over the whole pool."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import soc_tuner
    from repro.soc import VLSIFlow, get_workload, soc_metrics

    t0 = time.monotonic()
    space, pool = _pool(n_pool)
    ref = _reference_front(space, pool, "resnet50")
    checks, runs = [], {}
    for incremental in (False, True):
        t1 = time.monotonic()
        res = soc_tuner(space, pool, VLSIFlow(space, "resnet50"), T=T, n=n,
                        b=b, gp_steps=gp_steps, key=jax.random.PRNGKey(SEED),
                        reference_front=ref, incremental=incremental)
        label = "incremental" if incremental else "exact"
        runs[f"{label}_seconds"] = time.monotonic() - t1
        runs[f"{label}_final_adrs"] = float(res.history[-1]["adrs"])
        checks += _check_result(res, label, T=T)
    vals = np.asarray(space.values(pool), np.float32)
    layers = np.asarray(get_workload("resnet50"), np.float32)
    on_chip = np.asarray(soc_metrics(jnp.asarray(vals), jnp.asarray(layers)))
    cpu = jax.devices("cpu")[0]
    on_cpu = np.asarray(soc_metrics(jax.device_put(vals, cpu),
                                    jax.device_put(layers, cpu)))
    rel = float(np.max(np.abs(on_chip - on_cpu) / np.abs(on_cpu)))
    checks.append(_check(np.allclose(on_chip, on_cpu, rtol=1e-5, atol=0),
                         f"soc_metrics chip vs cpu over {n_pool} designs: "
                         f"max rel diff {rel:.3g} <= 1e-5"))
    _emit("paper", t0, checks, n_pool=n_pool, T=T, n=n, b=b, **runs)


def phase_large(n_pool: int = N_LARGE, T: int = 3, n: int = 30,
                b: int = 20) -> None:
    import jax
    from repro.core import soc_tuner
    from repro.soc import VLSIFlow

    t0 = time.monotonic()
    space, pool = _pool(n_pool)
    res = soc_tuner(space, pool, VLSIFlow(space, "resnet50"), T=T, n=n, b=b,
                    key=jax.random.PRNGKey(SEED), incremental=True,
                    pool_chunk="auto")
    checks = _check_result(res, f"{n_pool} candidates", T=T)
    _emit("large", t0, checks, n_pool=n_pool, T=T,
          peak_bytes_in_use=_peak_bytes(jax.devices()[0]),
          engine_stats={k: v for k, v in (res.engine_stats or {}).items()
                        if isinstance(v, (int, float))})


def _fleet(pool, space, scenarios, refs, T, mesh=None):
    from repro.core import fleet_tuner

    return fleet_tuner(space, pool, scenarios, T=T, reference_fronts=refs,
                       incremental=True, mesh=mesh)


def phase_fleet(n_pool: int = N_PAPER, T: int = 5, seeds: int = 2) -> None:
    from repro.core import FleetScenario

    t0 = time.monotonic()
    space, pool = _pool(n_pool)
    refs = {wl: _reference_front(space, pool, wl) for wl in WORKLOADS}
    scenarios = [FleetScenario(wl, seed=s) for wl in WORKLOADS
                 for s in range(seeds)]
    fr = _fleet(pool, space, scenarios, refs, T)
    checks = []
    for sc, res in zip(fr.scenarios, fr.results):
        checks += _check_result(res, sc.label, T=T)
    _emit("fleet", t0, checks, scenarios=len(scenarios), T=T,
          final_adrs=fr.final_adrs())


class _WorkerProbe:
    """Flow stand-in run in a pool worker: reports whether that worker's JAX
    sees the CPU alone (1.0 per flag) — it must never open the chip."""

    def __call__(self, idx):
        import jax
        import numpy as np

        return np.asarray([[jax.default_backend() == "cpu",
                            all(d.platform == "cpu" for d in jax.devices())]],
                          np.float64)


def phase_service(n_pool: int = 1024, T: int = 4, q: int = 2,
                  workers: int = 2, delay: float = 0.02) -> None:
    """``soc-service fleet`` with spawned process workers beside the parent
    that holds the chip; then a probe of what a worker's JAX can see."""
    import numpy as np
    from repro.service import FlowPool
    from repro.service.cli import main_fleet

    t0 = time.monotonic()
    checks = []
    with tempfile.TemporaryDirectory() as tmp:
        out = os.path.join(tmp, "fleet.json")
        rc = main_fleet([
            "--workloads", "resnet50,transformer", "--seeds", "0",
            "--n-pool", str(n_pool), "--pool-seed", str(SEED), "--T", str(T),
            "--q", str(q), "--min-done", "1", "--executor", "process",
            "--workers", str(workers), "--mock-flow-delay", str(delay),
            "--quiet", "--out", out])
        checks.append(_check(rc == 0, "soc-service fleet exited 0"))
        with open(out) as f:
            scen = json.load(f)["scenarios"]
    for label, rec in scen.items():
        y = np.asarray(rec["y"])
        rows = rec["evaluated_rows"]
        checks.append(_check(
            np.all(np.isfinite(y)) and np.all(y > 0)
            and len(set(rows)) == len(rows),
            f"{label}: {len(rows)} distinct rows, metrics finite positive"))
    pool = FlowPool(_WorkerProbe(), workload="probe", executor="process",
                    max_workers=1)
    try:
        (_, _, flags), = pool.collect([pool.submit(0, np.zeros(1))])
    finally:
        pool.close()
    checks.append(_check(np.all(flags == 1.0),
                         "a spawned flow worker's JAX sees only the CPU"))
    _emit("service", t0, checks, scenarios=len(scen), T=T, q=q,
          workers=workers)


def _assert_kernel(compiled, what: str) -> str:
    return _check("tpu_custom_call" in compiled.as_text(),
                  f"{what}: compiled HLO holds a tpu_custom_call")


def _aot(fn, *args):
    """Compile ``fn`` for ``args`` and run it once: (compiled, out, compile
    seconds, run seconds)."""
    import jax

    t0 = time.monotonic()
    compiled = jax.jit(fn).lower(*args).compile()
    t1 = time.monotonic()
    out = jax.block_until_ready(compiled(*args))
    return compiled, out, t1 - t0, time.monotonic() - t1


def _round_problem(key, nc: int, C: int, P: int, d: int = 26, m: int = 3,
                   S: int = 10):
    """A fused-round problem shaped like an engine round: train rows are the
    first P pool columns, L/beta come from the engine's own factorization."""
    import jax
    import jax.numpy as jnp
    from repro.core.engine import _chol_refactor, _train_beta
    from repro.core.gp import GPParams

    ks = jax.random.split(key, 6)
    pool_c = jax.random.normal(ks[0], (nc, C, d), jnp.float32)
    x = pool_c.reshape(nc * C, d)[:P]
    params = GPParams(
        jnp.log(jnp.sqrt(float(d))) + 0.3 * jax.random.normal(ks[1], (m, d)),
        0.2 * jax.random.normal(ks[2], (m,)),
        jnp.full((m,), jnp.log(1e-2), jnp.float32))
    L = _chol_refactor(params, x, jnp.zeros((P,), jnp.float32))
    beta = _train_beta(L, jax.random.normal(ks[3], (P, m), jnp.float32))
    y_mean = jnp.asarray([1.0, 50.0, 3.0], jnp.float32)[:m]
    y_std = jnp.asarray([0.5, 20.0, 1.5], jnp.float32)[:m]
    ystar = y_mean + y_std * (1.5 + 0.5 * jax.random.normal(ks[4], (S, m)))
    evalm = (jnp.arange(nc * C) < P).reshape(nc, C)
    base = jnp.arange(nc, dtype=jnp.int32) * C
    weights = jnp.asarray([1.0, 0.5, 2.0], jnp.float32)[:m]
    V0 = jnp.zeros((nc, m, P, C), jnp.float32)
    return params, L, V0, x, beta, ystar, pool_c, evalm, base, y_mean, \
        y_std, weights


def phase_kernels(n: int = N_TILED, Ps=(128, 512), n_large: int = N_LARGE,
                  C_large: int | None = None) -> None:
    """Each Pallas kernel compiled for the chip against the XLA route; the
    large pool is split as the engine's ``pool_chunk="auto"`` splits it
    unless ``C_large`` says otherwise."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels.backend import (auto_chunk, dominance_counts_auto,
                                       pairdist_auto, round_score_auto,
                                       soc_metrics_auto)
    from repro.soc import get_workload

    t0 = time.monotonic()
    checks, timing = [], {}
    space, pool = _pool(n)
    vals = jnp.asarray(space.values(pool), jnp.float32)

    def both(name, fn, *args):
        xla = _aot(functools.partial(fn, backend="xla"), *args)
        pal = _aot(functools.partial(fn, backend="pallas"), *args)
        checks.append(_assert_kernel(pal[0], name))
        timing[name] = {"compile_s": pal[2], "run_s": pal[3],
                        "xla_compile_s": xla[2], "xla_run_s": xla[3]}
        return xla[1], pal[1]

    for wl in WORKLOADS:
        layers = jnp.asarray(get_workload(wl), jnp.float32)
        want, got = both(f"systolic_eval/{wl}", soc_metrics_auto, vals,
                         layers)
        want, got = np.asarray(want), np.asarray(got)
        rel = float(np.max(np.abs(got - want) / np.abs(want)))
        checks.append(_check(np.allclose(got, want, rtol=1e-5, atol=0),
                             f"systolic_eval/{wl}: {n} designs, max rel "
                             f"diff {rel:.3g} <= 1e-5"))

    y = jnp.asarray(np.asarray(soc_metrics_auto(vals, layers)))
    want, got = both("pareto_count", dominance_counts_auto, y)
    checks.append(_check(np.array_equal(np.asarray(got), np.asarray(want)),
                         f"pareto_count: {n} dominance counts equal"))

    feats = jax.random.uniform(jax.random.PRNGKey(SEED), (n, 26))
    for P in Ps:
        want, got = both(f"pairdist/P{P}", pairdist_auto, feats, feats[:P])
        want, got = np.asarray(want), np.asarray(got)
        err = float(np.max(np.abs(got - want)))
        checks.append(_check(np.allclose(got, want, rtol=1e-5, atol=1e-5),
                             f"pairdist/P{P}: [{n},26]x[{P},26] max abs "
                             f"diff {err:.3g} within 1e-5"))

    C_large = auto_chunk(n_large) if C_large is None else C_large
    for P in Ps:
        for nc, C in ((1, n), (-(-n_large // C_large), C_large)):
            prob = _round_problem(jax.random.PRNGKey(P + nc), nc, C, P)
            V_full = None
            for s0 in (0, P // 2, P):
                args = list(prob)
                if s0:
                    args[2] = V_full
                fn = functools.partial(round_score_auto, s0=s0)
                (V_x, i_x), (V_p, i_p) = both(
                    f"round_fused/P{P}/nc{nc}/s0_{s0}", fn, *args)
                if s0 == 0:
                    V_full = V_x
                err = float(jnp.max(jnp.abs(V_p - V_x)))
                tag = f"round_fused P={P} nc={nc} C={C} s0={s0}"
                checks.append(_check(int(i_p) == int(i_x),
                                     f"{tag}: same pick {int(i_x)}"))
                checks.append(_check(
                    bool(jnp.allclose(V_p, V_x, rtol=1e-5, atol=1e-5)),
                    f"{tag}: V max abs diff {err:.3g} within 1e-5"))
    _emit("kernels", t0, checks, timing=timing)


def phase_four_chips(n_pool: int = N_PAPER, T: int = 5, seeds: int = 2,
                     n_dev: int = 4) -> None:
    """The sharded fleet over four chips against the same fleet unsharded
    on the first chip, pick for pick."""
    import jax
    import numpy as np
    from jax.sharding import Mesh
    from repro.core import FleetScenario

    t0 = time.monotonic()
    devs = jax.devices()
    _check(len(devs) >= n_dev, f"{n_dev} devices visible (got {len(devs)})")
    space, pool = _pool(n_pool)
    refs = {wl: _reference_front(space, pool, wl) for wl in WORKLOADS}
    scenarios = [FleetScenario(wl, seed=s, weights=w) for wl in WORKLOADS
                 for s in range(seeds) for w in ((1.0, 1.0, 1.0),
                                                 (3.0, 1.0, 1.0))]
    t1 = time.monotonic()
    plain = _fleet(pool, space, scenarios, refs, T)
    t_plain = time.monotonic() - t1
    mesh = Mesh(np.asarray(devs[:n_dev]), ("fleet",))
    t1 = time.monotonic()
    sharded = _fleet(pool, space, scenarios, refs, T, mesh=mesh)
    t_sharded = time.monotonic() - t1
    checks = []
    for sc, a, b in zip(scenarios, plain.results, sharded.results):
        checks += _check_result(b, f"sharded {sc.label}", T=T)
        checks.append(_check(
            np.array_equal(a.evaluated_rows, b.evaluated_rows),
            f"{sc.label}: sharded picks equal unsharded"))
    _emit("four_chips", t0, checks, scenarios=len(scenarios), T=T,
          unsharded_seconds=t_plain, sharded_seconds=t_sharded,
          peak_bytes_in_use=[_peak_bytes(d) for d in devs[:n_dev]])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded four-chip fleet phase")
    a = ap.parse_args(argv)
    # the cost-model check compares against the CPU backend in-process
    plat = os.environ.get("JAX_PLATFORMS")
    if plat and "cpu" not in plat.split(","):
        os.environ["JAX_PLATFORMS"] = plat + ",cpu"
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    try:
        info = phase_device()
    except SmokeFailure as e:
        print(f"chip_smoke: {e}", file=sys.stderr)
        return 2
    if a.four_chips:
        phase_four_chips()
    else:
        phase_paper()
        phase_large()
        phase_fleet()
        phase_service()
        phase_kernels()
    print(json.dumps({"ok": True, "device": info}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
