"""Generator ``soc_tuner``: one architect's explorations, back to back.

A traffic file that names this generator (``"generator": "soc_tuner"``)
gives the workload the flow evaluates. The window calls
``repro.core.soc_tuner`` with whole explorations, exploration ``i`` on key
``fold_in(PRNGKey(seed mod 2**32), i)``, each to its budget T or the
deadline. Each exploration's first two flow calls are its prologue (ICD
trials, TED init); every later call evaluates one round's pick.

The module gives the harness three things:

* :func:`build_context` — the cell's data from the seed, on the host;
* :func:`run_explorations` — the window's (and the warm-up's) traffic;
* :func:`replay_exploration` and :func:`key_rounds` — the engine's part of
  the check: the plain reference rebuilds checked rounds and ranks the
  program's picks under its own acquisition.
"""
from __future__ import annotations

import dataclasses
import time

import numpy as np

from benchlib import drive, reference as R

PROLOGUE_CALLS = 2


@dataclasses.dataclass
class Context:
    seed: int
    config: dict
    traffic: dict
    settings: dict          # protocol and engine settings over the defaults
    space: object
    pool: np.ndarray
    row_of: dict
    flow: object
    reference_front: np.ndarray | None
    tuner_kwargs: dict


def build_context(cell, seed: int) -> Context:
    """The pool of distinct designs drawn from the seed, the reference
    front (from the reference cost model) where the configuration keeps one,
    and the program's flow for the traffic's workload."""
    from repro.core import make_space
    from repro.soc import VLSIFlow

    cfg, traffic = cell.config, cell.traffic
    workload = traffic["workload"]
    pool = R.sample_pool(seed, int(cfg["n_pool"]))
    front = (R.pareto_front(R.soc_metrics(pool, R.workload(workload)))
             if cfg.get("reference_front") else None)
    space = make_space()
    engine = dict(cfg.get("engine", {}))
    return Context(
        seed=seed, config=cfg, traffic=traffic,
        settings=R.settings(cfg["protocol"], engine), space=space, pool=pool,
        row_of={r.tobytes(): i for i, r in enumerate(pool)},
        flow=VLSIFlow(space, workload), reference_front=front,
        tuner_kwargs={**cfg["protocol"], **engine})


def run_explorations(ctx: Context, closed, *, spans: bool = False,
                     out: list | None = None) -> list:
    """Explorations back to back until ``closed()``; returns them all, the
    last one cut by the deadline (appended to ``out`` as they start, where
    given)."""
    from repro.core import soc_tuner

    workload = ctx.traffic["workload"]
    tap = drive.FlowTap(ctx.flow, closed, PROLOGUE_CALLS, spans=spans)
    explorations = [] if out is None else out
    i = 0
    while not closed():
        exp = drive.Exploration(i, drive.exploration_key(ctx.seed, i),
                                time.monotonic(), workload, PROLOGUE_CALLS)
        explorations.append(exp)
        span = drive.Span("exploration", spans)
        tap.begin(exp)
        try:
            exp.result = soc_tuner(ctx.space, ctx.pool, tap, key=exp.key,
                                   reference_front=ctx.reference_front,
                                   **ctx.tuner_kwargs)
            exp.final_adrs = exp.result.history[-1].get("adrs")
        except drive.WindowClosed:
            pass
        except Exception as e:  # a round that raises counts as failed
            exp.error = f"{type(e).__name__}: {e}"
        finally:
            tap.end()
            span.close()
        i += 1
    return explorations


def _rows_after_prologue(exp) -> int:
    return len({d.tobytes() for c in exp.calls[:PROLOGUE_CALLS]
                for d in c.designs})


def key_rounds(ctx: Context, exp) -> list:
    """The rounds of ``exp`` whose training set enters a new 8-row bucket,
    the first round with them: where the incremental engine refactors and
    where the exact engine first runs a bucket's programs."""
    if len(exp.calls) < PROLOGUE_CALLS:
        return []
    n0 = _rows_after_prologue(exp)
    out, prev = [], None
    for rnd in exp.rounds:
        P = -(-(n0 + rnd.index) // R.BUCKET) * R.BUCKET
        if P != prev:
            out.append(rnd.index)
        prev = P
    return out


class _Schedule:
    """The engine's documented fit schedule for one arithmetic: exact — a
    cold ``gp_steps`` fit every round; incremental — cold on the first
    round, then ``warm_steps`` from the last fit, with the posterior under
    the parameters of the last refactor (first round, a new 8-row bucket,
    or a drift of any log-parameter beyond ``drift_tol``)."""

    def __init__(self, ar, settings: dict, m: int):
        self.ar = ar
        self.incremental = bool(settings["incremental"])
        self.gp_steps = int(settings["gp_steps"])
        self.warm_steps = int(settings["warm_steps"])
        self.drift_tol = float(settings["drift_tol"])
        self.m = m
        self.params = None
        self.params_ref = None
        self.P = 0

    def step(self, x, yn, mask, needed: bool):
        """Posterior parameters of this round, or None where the round is
        not checked and the schedule keeps no state across rounds."""
        d = x.shape[1]
        if not self.incremental:
            if not needed:
                return None
            return R.fit(self.ar, R.default_params(self.m, d), x, yn, mask,
                         self.gp_steps)
        first = self.params is None
        P = x.shape[0]
        if first:
            self.params = R.fit(self.ar, R.default_params(self.m, d), x, yn,
                                mask, self.gp_steps)
        else:
            self.params = R.fit(self.ar, self.params, x, yn, mask,
                                self.warm_steps)
        drift = 0.0 if first else max(
            float(np.max(np.abs(self.params[k] - self.params_ref[k])))
            for k in self.params)
        if first or P != self.P or drift > self.drift_tol:
            self.params_ref = {k: v.copy() for k, v in self.params.items()}
        self.P = P
        return self.params_ref


def _acquisition(ar, params, x, yn, ym, ys, mask, pool_icd, sub, eps,
                 evaluated) -> np.ndarray:
    post = R.Posterior(ar, params, x, yn, mask)
    ystar = post.frontier_ystar(pool_icd[sub], eps, ym, ys)
    mu, sd = post.moments(pool_icd)
    a = R.mes(mu * ys + ym, sd * ys, ystar).astype(np.float64)
    a[np.asarray(evaluated)] = -np.inf
    return a


def replay_exploration(ctx: Context, exp, check: set, ariths: list,
                       record) -> str | None:
    """Replay ``exp`` up to its last round in ``check`` under each
    arithmetic (the first is the reference), and at each checked round call
    ``record(round, a_ref, choices)`` with the reference's acquisition and
    the choices judged by it: the program's pick, then each other
    arithmetic's own argmax. Returns why the exploration cannot be replayed,
    or None."""
    import jax

    st = ctx.settings
    N = ctx.pool.shape[0]
    layers = R.workload(exp.workload)
    key = exp.key
    k_icd, _k_init, key = jax.random.split(key, 3)
    trial_rows = np.asarray(jax.random.choice(
        k_icd, N, shape=(min(int(st["n"]), N),), replace=False))
    calls = exp.calls
    if not np.array_equal(calls[0].designs, ctx.pool[trial_rows]):
        return f"exploration {exp.index}: ICD trials are not the key's"
    y_trial = R.soc_metrics(ctx.pool[trial_rows], layers)
    v = R.icd_importance(ctx.pool[trial_rows], y_trial)
    pool_icd = R.icd_transform(ctx.pool, v, float(st["v_th"]))
    evaluated = [ctx.row_of[d.tobytes()] for d in calls[1].designs]
    y = R.soc_metrics(calls[1].designs, layers)
    seen = set(evaluated)
    fresh = [i for i, r in enumerate(trial_rows) if int(r) not in seen]
    evaluated += [int(trial_rows[i]) for i in fresh]
    y = np.concatenate([y, y_trial[fresh]], 0)

    m = y.shape[1]
    scheds = [_Schedule(ar, st, m) for ar in ariths]
    fs = int(st["frontier_subset"])
    s = int(st["s_frontiers"])
    last = max(check)
    for r, call in enumerate(calls[PROLOGUE_CALLS:]):
        if r > last:
            break
        key, _k_fit, k_acq, k_sub = jax.random.split(key, 4)
        x, yn, ym, ys, mask = R.padded_training(pool_icd[evaluated], y)
        needed = r in check
        params = [sc.step(x, yn, mask, needed) for sc in scheds]
        pick = ctx.row_of[call.designs[0].tobytes()]
        if needed:
            sub = (np.asarray(jax.random.choice(k_sub, N, shape=(fs,),
                                                replace=False))
                   if N > fs else np.arange(N))
            keys = jax.random.split(k_acq, m)
            eps = np.stack([np.asarray(jax.random.normal(k, (len(sub), s)))
                            for k in keys])
            acq = [_acquisition(sc.ar, pa, x, yn, ym, ys, mask, pool_icd,
                                sub, eps, evaluated)
                   for sc, pa in zip(scheds, params)]
            choices = [("program", pick)] + [
                (ar.name, int(np.argmax(a)))
                for ar, a in zip(ariths[1:], acq[1:])]
            record(r, int(x.shape[0] - mask.sum()), acq[0], choices)
        evaluated.append(pick)
        y = np.concatenate([y, R.soc_metrics(call.designs, layers)], 0)
    return None
