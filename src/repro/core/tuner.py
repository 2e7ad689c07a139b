"""Algorithm 3 — SoC-Tuner(X, T, n, u, b, v_th): the full exploration loop.

Operates over a finite candidate *pool* (the paper's experiments sample 2500
design points and treat their flow metrics as the metric space); the flow is
any callable ``idx [k,d] -> y [k,m]`` — the bundled VLSI-flow surrogate, the
simplified analytical model, or a real flow runner.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs import log_progress, span

from .engine import BOEngine
from .icd import icd_from_data
from .pareto import adrs, pareto_mask
from .propose import (PROPOSER_FOLD, ProposerConfig, ProposerStats,
                      propose_and_replace)
from .sampling import soc_init, transform_to_icd
from .space import DesignSpace

__all__ = ["TunerResult", "soc_tuner", "frontier_subset_rows",
           "explore_prologue"]

FlowFn = Callable[[np.ndarray], np.ndarray]


def icd_trial_rows(key: jax.Array, n_pool: int, n: int
                   ) -> tuple[np.ndarray, jax.Array]:
    """Alg. 3 line 1 setup: draw the ``n`` ICD trial pool-rows and return
    them with the advanced key. Shared with the fleet runner so both paths
    consume the PRNG stream identically."""
    k_icd, _k_init, key = jax.random.split(key, 3)
    rows = np.asarray(jax.random.choice(
        k_icd, n_pool, shape=(min(n, n_pool),), replace=False))
    return rows, key


def merge_trial_evals(evaluated: "list[int]", y_init: np.ndarray,
                      trial_rows: np.ndarray, trial_y: np.ndarray,
                      reuse_icd_trials: bool) -> tuple["list[int]", np.ndarray]:
    """Alg. 3 line 4 bookkeeping: seed the GP with the TED-init evaluations
    plus (optionally) the ICD trial evaluations not already covered. Shared
    with the fleet runner — the evaluation order defines the trajectory."""
    y_list = [np.asarray(y_init)]
    if reuse_icd_trials:
        seen = set(evaluated)  # built ONCE, not per element
        fresh, keep = [], []
        for i, r in enumerate(trial_rows):
            r = int(r)
            if r not in seen:
                seen.add(r)
                fresh.append(r)
                keep.append(i)
        evaluated = evaluated + fresh
        y_list.append(np.asarray(trial_y)[keep])
    return evaluated, np.concatenate(y_list, axis=0)


def round_record(y: np.ndarray, n_evaluated: int, round_i: int,
                 reference_front: np.ndarray | None,
                 wall_s: float | None = None) -> dict:
    """One history entry for round ``round_i``.

    Shared with the fleet runner so sequential and fleet histories always
    carry the same keys (fig7 reads them interchangeably). ``wall_s``
    (optional) records the round's wall time — ``engine_bench`` reads it."""
    front = _front(y)
    rec = {"round": round_i, "evaluations": n_evaluated,
           "pareto_size": int(front.sum())}
    if reference_front is not None:
        rec["adrs"] = adrs(reference_front, y[front])
    if wall_s is not None:
        rec["wall_s"] = wall_s
    return rec


def frontier_subset_rows(key: jax.Array, n_pool: int,
                         frontier_subset: int) -> np.ndarray | None:
    """Rows used for the O(q³) joint frontier sampling, or ``None`` for the
    whole pool. Shared by the sequential loop and the fleet runner so a
    fleet-of-one draws the exact same subset as ``soc_tuner``."""
    if n_pool > frontier_subset:
        return np.asarray(jax.random.choice(
            key, n_pool, shape=(frontier_subset,), replace=False))
    return None


def explore_prologue(space: DesignSpace, pool_idx: np.ndarray, flow: FlowFn,
                     key: jax.Array, *, n: int, mu: float, b: int,
                     v_th: float, use_kernels: bool = False,
                     reuse_icd_trials: bool = True):
    """Algorithm 3 lines 1-4: ICD trials → importance → prune/TED-init →
    seed evaluations. Returns ``(key, v, pruned, pool_icd, evaluated, y)``.

    Shared between :func:`soc_tuner` and the exploration service
    (``repro.service.runner``) — operation-for-operation the historical
    prologue, so both drivers consume the PRNG stream and the flow budget
    identically. A checkpoint resume replays everything after the flow
    calls from the stored ``v`` instead (see :func:`_prologue_from_v`).
    """
    with span("tuner.prologue"):
        N = pool_idx.shape[0]
        # Line 1: v = ICD(X, n). Trials are drawn from the pool so their
        # metrics can seed the GP (the paper's flow budget accounting does the
        # same: the n importance trials are real evaluations).
        trial_rows, key = icd_trial_rows(key, N, n)
        with span("tuner.flow"):
            trial_y = np.asarray(flow(pool_idx[trial_rows]))
        v = icd_from_data(space, pool_idx[trial_rows], trial_y)

        # Line 2: Z = SoC-Init(X, µ, b, v, v_th) (prune + ICD transform + TED)
        init_rows, pruned, pool_icd = soc_init(
            space, pool_idx, v, v_th=v_th, b=b, mu=mu,
            use_kernel=use_kernels)
        pool_icd = jnp.asarray(pool_icd, jnp.float32)

        # Line 4: y <- VLSIFlow(Z)
        evaluated: list[int] = list(dict.fromkeys(int(r) for r in init_rows))
        with span("tuner.flow"):
            y_init = np.asarray(flow(pool_idx[np.asarray(evaluated)]))
        evaluated, y = merge_trial_evals(evaluated, y_init, trial_rows,
                                         trial_y, reuse_icd_trials)
        return key, v, pruned, pool_icd, evaluated, y


def _prologue_from_v(space: DesignSpace, pool_idx: np.ndarray, v: np.ndarray,
                     *, mu: float, b: int, v_th: float,
                     use_kernels: bool = False):
    """Rebuild the flow-free prologue outputs from a checkpointed importance
    vector: ``soc_init`` is deterministic in ``(space, pool, v)``, so resume
    never re-pays the trial/init flow evaluations."""
    _, pruned, pool_icd = soc_init(space, pool_idx, v, v_th=v_th, b=b, mu=mu,
                                   use_kernel=use_kernels)
    return pruned, jnp.asarray(pool_icd, jnp.float32)


def _pool_fingerprint(pool_idx: np.ndarray) -> str:
    """Cheap content hash of the candidate pool — a resumed run must explore
    the identical pool or the stored engine state is meaningless."""
    import hashlib

    return hashlib.sha1(np.ascontiguousarray(
        np.asarray(pool_idx, np.int64)).tobytes()).hexdigest()


@dataclasses.dataclass
class TunerResult:
    space: DesignSpace                # pruned space actually explored
    v: np.ndarray                     # ICD importance vector (Alg. 1)
    evaluated_rows: np.ndarray        # pool-row indices, in evaluation order
    y: np.ndarray                     # metrics for evaluated rows [k, m]
    pareto_rows: np.ndarray           # subset of evaluated_rows on the front
    pareto_y: np.ndarray              # their metrics (the learned Y*)
    history: list[dict]               # per-round log (for ADRS curves)
    wall_s: float
    engine_stats: dict | None = None  # BOEngine counters (refactors, ...)

    def pareto_idx(self, pool_idx: np.ndarray) -> np.ndarray:
        """Design-point index vectors X* restored to the original space
        (Alg. 3 line 11)."""
        return np.asarray(pool_idx)[self.pareto_rows]


def _front(y: np.ndarray) -> np.ndarray:
    return np.asarray(pareto_mask(jnp.asarray(np.asarray(y, np.float64))))


def soc_tuner(
    space: DesignSpace,
    pool_idx: np.ndarray,
    flow: FlowFn,
    *,
    T: int = 40,
    n: int = 30,
    mu: float = 0.1,
    b: int = 20,
    v_th: float = 0.07,
    s_frontiers: int = 10,
    frontier_subset: int = 512,
    gp_steps: int = 150,
    key: jax.Array | None = None,
    reference_front: np.ndarray | None = None,
    reuse_icd_trials: bool = True,
    use_kernels: bool = False,
    weights: np.ndarray | None = None,
    incremental: bool = False,
    warm_start: bool | None = None,
    warm_steps: int | None = None,
    drift_tol: float = 1.0,
    pool_chunk: int | str | None = None,
    q: int = 1,
    fantasy: str = "mean",
    checkpoint_dir: str | None = None,
    checkpoint_every: int = 1,
    resume: bool = False,
    proposer=None,
    verbose: bool = False,
) -> TunerResult:
    """Run SoC-Tuner over ``pool_idx`` [N, d] candidate designs.

    Follows Algorithm 3 line by line; ``reference_front`` (the real Pareto
    front of the pool, if known) enables per-round ADRS logging for Fig. 7(a).
    ``weights`` [m] (optional) biases the acquisition's per-objective
    information gain (Eq. 9 scalarization) — exploration focus, not a change
    to the Pareto bookkeeping.

    The per-round surrogate work runs on a persistent :class:`BOEngine`.
    ``incremental=False`` (the fidelity default) executes the historical
    from-scratch round and reproduces the seed trajectory bit-for-bit;
    ``incremental=True`` enables warm-started fits, rank-k Cholesky updates,
    cached pool covariances and device-side selection — same math to
    numerical tolerance, measurably faster per round (see
    ``benchmarks/engine_bench.py``). ``warm_start`` (default: follow
    ``incremental``) plumbs the previous round's ``GPParams`` into ``fit_gp``
    even on the from-scratch path; ``warm_steps``/``drift_tol`` tune the
    incremental engine's fit schedule and refactorization policy.
    ``pool_chunk`` (int | ``"auto"``; requires ``incremental=True``) streams
    the engine's O(N) pool state in column chunks so ``n_pool`` can grow to
    10⁵–10⁶ candidates — identical selections at any chunk size; see
    ``docs/scaling.md``. Under a ``jax.profiler`` trace every round and its
    layers are spans (``tuner.round``, ``engine.select``, ...;
    :mod:`repro.obs.spans`) beside the device ops.

    ``q`` (requires ``incremental=True`` when > 1) selects q candidates per
    round via fantasy updates (``BOEngine.select_q``; ``fantasy`` picks the
    imputation rule) and evaluates them in ONE flow call — ``q=1`` is the
    historical one-pick round, bit-for-bit. ``checkpoint_dir`` writes a
    versioned snapshot of the full exploration state (engine, RNG key,
    history) every ``checkpoint_every`` rounds; ``resume=True`` continues a
    killed run from the latest snapshot *bit-exactly*, without re-paying any
    flow evaluation (see ``docs/service.md``).

    ``proposer`` (None | bool | dict | :class:`ProposerConfig`; default OFF,
    requires ``incremental=True``) enables the between-round perturbation
    proposer: after each round the lowest-scoring unevaluated pool columns
    are replaced by novel designs sampled near the current Pareto front
    (:mod:`repro.core.propose`). The proposer draws its randomness through
    ``jax.random.fold_in`` off the driver key, so a proposer-off run stays
    byte-identical to one without the knob; checkpoints additionally carry
    the live (edited) pool and resume bit-exactly.
    """
    t0 = time.monotonic()
    key = jax.random.PRNGKey(0) if key is None else key
    pool_idx = np.asarray(pool_idx)
    N = pool_idx.shape[0]
    pcfg = ProposerConfig.from_arg(proposer)
    pstats = ProposerStats()
    if pcfg.enabled:
        if not incremental:
            raise ValueError(
                "proposer requires incremental=True: victim scoring runs on "
                "the incremental engine's cached round state (pool_scores)")
        pool_idx = np.array(pool_idx)  # private copy — the proposer edits it
    if q < 1:
        raise ValueError(f"q must be >= 1, got {q}")
    if q > 1 and not incremental:
        raise ValueError(
            "q > 1 requires incremental=True: fantasy q-batch selection "
            "runs on the incremental engine (checked up front so no flow "
            "budget is spent on a run that cannot start)")
    # everything that defines the trajectory must survive a resume intact
    # (T may grow: it only decides when the loop stops)
    config = {"q": int(q), "n": int(n), "b": int(b), "mu": float(mu),
              "v_th": float(v_th), "gp_steps": int(gp_steps),
              "s_frontiers": int(s_frontiers),
              "frontier_subset": int(frontier_subset), "fantasy": fantasy,
              "incremental": bool(incremental), "pool_chunk": pool_chunk,
              "warm_start": warm_start, "warm_steps": warm_steps,
              "drift_tol": float(drift_tol),
              "reuse_icd_trials": bool(reuse_icd_trials),
              "weights": (None if weights is None else
                          [float(x) for x in np.asarray(weights).reshape(-1)])}
    if pcfg.enabled:
        # Only joins the trajectory guard when ON so proposer-less
        # checkpoints written before this knob existed keep resuming.
        config["proposer"] = pcfg.as_dict()
    # Fingerprint of the pool AS PASSED — the proposer edits pool_idx, but
    # a resuming caller passes the original pool, so the guard pins that.
    pool_fp = _pool_fingerprint(pool_idx)

    snap = None
    if resume and checkpoint_dir:
        from repro.service.checkpoint import load_latest_validated

        snap = load_latest_validated(
            checkpoint_dir, driver="soc_tuner", pool=pool_fp, config=config)

    if snap is None:
        key, v, pruned, pool_icd, evaluated, y = explore_prologue(
            space, pool_idx, flow, key, n=n, mu=mu, b=b, v_th=v_th,
            use_kernels=use_kernels, reuse_icd_trials=reuse_icd_trials)
    else:
        v = np.asarray(snap["v"])
        if pcfg.enabled and "pool_live" in snap:
            # Continue on the edited pool; evaluated rows are immutable so
            # every recorded pick still denotes the design it scored.
            pool_idx = np.array(snap["pool_live"])
            pstats = ProposerStats.from_dict(snap["proposer_stats"])
        pruned, pool_icd = _prologue_from_v(space, pool_idx, v, mu=mu, b=b,
                                            v_th=v_th, use_kernels=use_kernels)
        evaluated = [int(r) for r in snap["evaluated"]]
        y = np.asarray(snap["y"], np.float32)
        key = jnp.asarray(snap["key"])

    history: list[dict] = [] if snap is None else list(snap["history"])
    t_round = time.monotonic()

    def log_round(i: int):
        nonlocal t_round
        now = time.monotonic()
        log_progress(history, y, len(evaluated), i, reference_front,
                     verbose=verbose, tag="soc-tuner",
                     wall_s=now - t_round)
        t_round = now

    start_round = 0 if snap is None else int(snap["round"])
    if snap is None:
        log_round(0)

    # Lines 5-10: BO loop, run on a persistent device-resident engine. The
    # engine internally negates targets (paper metrics are minimized, MES
    # maximizes) and owns the never-re-evaluate mask + argmax (Line 7).
    w = None if weights is None else jnp.asarray(weights, jnp.float32)
    engine = BOEngine(pool_icd, incremental=incremental,
                      warm_start=warm_start, gp_steps=gp_steps,
                      warm_steps=warm_steps, drift_tol=drift_tol,
                      s_frontiers=s_frontiers, weights=w,
                      pool_chunk=pool_chunk)
    if snap is None:
        engine.observe(evaluated, y)
    else:
        engine.load_state_dict(snap["engine"])

    def save_checkpoint(round_i: int) -> None:
        from repro.service.checkpoint import (prune_snapshots, save_snapshot,
                                              snapshot_path)

        d = {
            "driver": "soc_tuner", "round": round_i,
            "pool": pool_fp, "config": config,
            "key": np.asarray(key), "v": np.asarray(v),
            "evaluated": np.asarray(evaluated, np.int64), "y": y,
            "history": history, "engine": engine.state_dict()}
        if pcfg.enabled:
            d["pool_live"] = np.asarray(pool_idx)
            d["proposer_stats"] = pstats.as_dict()
        save_snapshot(snapshot_path(checkpoint_dir, round_i), d)
        prune_snapshots(checkpoint_dir)

    for it in range(start_round, T):
        with span("tuner.round", round=it + 1):
            with span("tuner.keys"):
                key, k_fit, k_acq, k_sub = jax.random.split(key, 4)
                del k_fit  # reserved slot — keeps the key schedule stable
                # Frontier sampling over a subset (O(q³) Cholesky), scoring
                # over all.
                sub = frontier_subset_rows(k_sub, N, frontier_subset)
            picks = engine.select_q(k_acq, q, sub_rows=sub, fantasy=fantasy)

            # Line 8: evaluate and append (one flow call for the whole batch).
            with span("tuner.flow"):
                y_new = np.asarray(flow(pool_idx[np.asarray(picks)]))
            evaluated.extend(picks)
            y = np.concatenate([y, y_new], axis=0)
            engine.observe(picks, y_new)
            log_round(it + 1)
            # Between-round proposal (default off): refresh the weakest pool
            # columns before the next round spends acquisition budget on
            # them. fold_in keys it off the carried key WITHOUT advancing the
            # split schedule, and runs before the checkpoint so a killed run
            # resumes on exactly the pool the next round would have seen.
            # Runs after the final round too — T may grow across resumes, so
            # the proposal schedule must not depend on it.
            if pcfg.enabled and (it + 1) % pcfg.every == 0:
                with span("tuner.propose"):
                    out = propose_and_replace(
                        engine, space,
                        jax.random.fold_in(key, PROPOSER_FOLD + it),
                        pool_idx, cfg=pcfg,
                        encode_cols=lambda c: transform_to_icd(
                            space, pruned.apply_pins(jnp.asarray(c)), v),
                        evaluated=[evaluated], ys=[y], stats=pstats)
                if out is not None:
                    pool_idx[out.victims] = out.new_idx
            if checkpoint_dir and (it + 1) % checkpoint_every == 0:
                with span("tuner.checkpoint"):
                    save_checkpoint(it + 1)

    front = _front(y)
    rows = np.asarray(evaluated)
    stats_d = engine.stats.as_dict()
    if pcfg.enabled:
        stats_d["proposer"] = pstats.as_dict()
    return TunerResult(
        space=pruned, v=np.asarray(v), evaluated_rows=rows, y=y,
        pareto_rows=rows[front], pareto_y=y[front], history=history,
        wall_s=time.monotonic() - t0, engine_stats=stats_d)
