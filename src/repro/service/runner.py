"""The exploration service loop: async q-batch BO over a worker pool.

:func:`service_tuner` is Algorithm 3 rebuilt for a production flow budget:

- per refill it asks the incremental engine for up to ``q`` candidates via
  **fantasy updates** (``BOEngine.select_q`` — in-flight picks are
  fantasized, new picks are chosen one rank-1 update apart, and the sampled
  frontier y* is drawn once per refill and frozen across the whole chain,
  so a refill pays exactly one O(q³) joint frontier draw);
- picks are dispatched to a :class:`~repro.service.pool.FlowPool` of
  concurrent workers and **completions are fed back as they land** —
  with ``min_done=1`` (the default) a new selection round starts as soon as
  ONE evaluation returns, while the other q-1 stay pending (post-freeze-y*
  this fully-async mode beats the ``min_done=q`` barrier — see
  ``BENCH_fleet_service.json``; the multi-scenario twin is
  :func:`repro.service.fleet_runner.fleet_service`);
- every completion batch writes a **versioned atomic checkpoint** (engine
  state, RNG key, trajectory); a SIGKILL'd run resumed with ``resume=True``
  reproduces the uninterrupted trajectory bit-exactly;
- all evaluations dedup against the content-addressed on-disk flow cache.

With ``q=1`` and the inline executor the loop degenerates to exactly
``soc_tuner``'s sequential round — bit-identical picks, same PRNG stream,
same flow calls (pinned by ``tests/test_service.py``). ``T`` counts **flow
evaluations consumed by the BO phase** (for q=1 that equals rounds, so the
budget is comparable across q).

Determinism: with ``ordered=True`` (default) completions are *observed* in
submission order regardless of which worker finishes first — workers still
run concurrently; only the feed-back order is pinned — so the trajectory,
and therefore every checkpoint, is independent of worker timing.
``ordered=False`` observes opportunistically (lowest latency, trajectory
then depends on arrival order; checkpoints remain self-consistent).
"""
from __future__ import annotations

import os
import signal
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.engine import BOEngine, FANTASY_MODES
from repro.core.propose import (PROPOSER_FOLD, ProposerConfig, ProposerStats,
                                propose_and_replace)
from repro.core.sampling import transform_to_icd
from repro.core.tuner import (TunerResult, _front, _pool_fingerprint,
                              _prologue_from_v, explore_prologue,
                              frontier_subset_rows)
from repro.obs import EventLog, MetricsRegistry, log_progress

from .checkpoint import (load_latest_validated, prune_snapshots,
                         save_snapshot, snapshot_path)
from .flowcache import CachedFlow, FlowDiskCache
from .pool import FlowPool

__all__ = ["service_tuner"]



def service_tuner(
    space,
    pool_idx: np.ndarray,
    flow,
    *,
    workload: str = "resnet50",
    T: int = 40,
    q: int = 1,
    fantasy: str = "mean",
    min_done: int = 1,
    ordered: bool = True,
    max_workers: int | None = None,
    executor="process",
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
    weights: np.ndarray | None = None,
    incremental: bool = True,
    warm_start: bool | None = None,
    warm_steps: int | None = None,
    drift_tol: float = 1.0,
    pool_chunk: int | str | None = None,
    bucket: int | None = None,
    cache_dir: str | None = None,
    checkpoint_dir: str | None = None,
    checkpoint_every: int = 1,
    resume: bool = False,
    proposer=None,
    verbose: bool = False,
    metrics: MetricsRegistry | None = None,
    events: EventLog | str | None = None,
    _kill_after: int | None = None,
) -> TunerResult:
    """Run the exploration service; returns ``soc_tuner``'s result layout.

    ``T`` = BO-phase flow-evaluation budget; ``q`` = max concurrent
    evaluations in flight; ``min_done`` = completions to wait for before the
    next refill (1 = fully async, ``q`` = synchronous round barrier).
    ``executor`` ∈ {"process", "thread", "inline"} or an Executor instance;
    ``max_workers`` defaults to ``q``. ``cache_dir`` attaches the on-disk
    flow cache; ``checkpoint_dir``/``resume`` make the run restartable (see
    module docstring). ``incremental`` defaults to True — the engine the
    service is built for; q>1 requires it. ``bucket`` overrides the engine's
    jit-cache pad bucket (larger buckets = fewer recompiles on long runs).
    ``_kill_after`` is a test hook: SIGKILL this process right after the
    checkpoint that covers that many BO evaluations (exercises crash-resume).

    ``proposer`` (None | bool | dict | ``ProposerConfig``) enables the
    between-round perturbation proposer: after every ``every``-th completed
    evaluation the weakest unevaluated, non-pending pool columns are
    replaced by designs sampled near the current front
    (:mod:`repro.core.propose`). Default off — the historical trajectory
    stays byte-identical; checkpoints carry the live pool so a SIGKILL'd
    proposer run still resumes bit-exactly.

    Telemetry (all host-side, zero trajectory perturbation — see
    ``repro.obs``): ``metrics`` joins an existing registry (one is created
    otherwise), ``events`` is an :class:`repro.obs.EventLog` or a path to
    open one (a path is closed on exit; a resumed run appends a new
    generation). Under a ``jax.profiler`` trace the engine's and the
    prologue's spans (:mod:`repro.obs.spans`) land beside the device ops.
    """
    t0 = time.monotonic()
    metrics = MetricsRegistry() if metrics is None else metrics
    _ev_owned = isinstance(events, str)
    ev = EventLog(events, run="service_tuner") if _ev_owned else events
    if q < 1:
        raise ValueError(f"q must be >= 1, got {q}")
    if q > 1 and not incremental:
        raise ValueError(
            "q > 1 requires incremental=True: fantasy q-batch selection "
            "runs on the incremental engine (checked up front so no flow "
            "budget is spent on a run that cannot start)")
    if min_done < 1 or min_done > q:
        raise ValueError(f"min_done must be in [1, q={q}], got {min_done}")
    if fantasy not in FANTASY_MODES:
        raise ValueError(f"fantasy must be one of {FANTASY_MODES}")
    key = jax.random.PRNGKey(0) if key is None else key
    pool_idx = np.asarray(pool_idx)
    pcfg = ProposerConfig.from_arg(proposer)
    pstats = ProposerStats()
    if pcfg.enabled:
        if not incremental:
            raise ValueError(
                "proposer requires incremental=True: victim scoring runs on "
                "the incremental engine's cached round state (pool_scores)")
        pool_idx = np.array(pool_idx)  # private copy — the proposer edits it
    N = pool_idx.shape[0]
    # Everything that defines the trajectory must survive a resume intact;
    # ``T`` is stored for reference but exempt from the resume guard —
    # extending the budget is a legitimate ops action (it only clamps
    # refill sizes near the end of the budget).
    config = {"T": int(T), "q": int(q), "n": int(n), "b": int(b),
              "mu": float(mu), "v_th": float(v_th), "gp_steps": int(gp_steps),
              "s_frontiers": int(s_frontiers),
              "frontier_subset": int(frontier_subset), "fantasy": fantasy,
              "min_done": int(min_done), "ordered": bool(ordered),
              "incremental": bool(incremental), "workload": str(workload),
              "warm_start": warm_start, "warm_steps": warm_steps,
              "drift_tol": float(drift_tol), "pool_chunk": pool_chunk,
              "reuse_icd_trials": bool(reuse_icd_trials),
              "weights": (None if weights is None else
                          [float(x) for x in np.asarray(weights).reshape(-1)])}
    if pcfg.enabled:
        # Joins the trajectory guard only when ON — proposer-less
        # checkpoints written before this knob existed keep resuming.
        config["proposer"] = pcfg.as_dict()
    # Fingerprint of the pool AS PASSED — the proposer edits pool_idx, but
    # a resuming caller passes the original pool, so the guard pins that.
    pool_fp = _pool_fingerprint(pool_idx)

    snap = None
    if resume and checkpoint_dir:
        snap = load_latest_validated(
            checkpoint_dir, driver="service_tuner", pool=pool_fp,
            config={k: v for k, v in config.items() if k != "T"})
        if snap is not None and verbose:
            print(f"[service] resuming at {int(snap['done'])}/{T} "
                  "evaluations")
        if snap is not None and pcfg.enabled and "pool_live" in snap:
            # Continue on the edited pool; evaluated rows are immutable so
            # every recorded pick still denotes the design it scored.
            pool_idx = np.array(snap["pool_live"])
            pstats = ProposerStats.from_dict(snap["proposer_stats"])

    disk = FlowDiskCache(cache_dir) if cache_dir else None
    # Prologue flow calls go through the disk cache too (a restart re-pays
    # nothing even without a checkpoint); the pool consults it per pick.
    pro_flow = flow if disk is None else CachedFlow(flow, disk, workload)
    if snap is None:
        key, v, pruned, pool_icd, evaluated, y = explore_prologue(
            space, pool_idx, pro_flow, key, n=n, mu=mu, b=b, v_th=v_th,
            reuse_icd_trials=reuse_icd_trials)
    else:
        v = np.asarray(snap["v"])
        pruned, pool_icd = _prologue_from_v(space, pool_idx, v, mu=mu, b=b,
                                            v_th=v_th)
        evaluated = [int(r) for r in snap["evaluated"]]
        y = np.asarray(snap["y"], np.float32)
        key = jnp.asarray(snap["key"])

    w = None if weights is None else jnp.asarray(weights, jnp.float32)
    engine_kw = dict(incremental=incremental, warm_start=warm_start,
                     gp_steps=gp_steps, warm_steps=warm_steps,
                     drift_tol=drift_tol, s_frontiers=s_frontiers,
                     weights=w, pool_chunk=pool_chunk)
    if bucket is not None:
        engine_kw["bucket"] = int(bucket)
    engine = BOEngine(pool_icd, **engine_kw)
    if snap is None:
        engine.observe(evaluated, y)
    else:
        engine.load_state_dict(snap["engine"])

    history: list[dict] = [] if snap is None else list(snap["history"])
    done = 0 if snap is None else int(snap["done"])
    t_round = time.monotonic()

    def log_round(i: int) -> None:
        nonlocal t_round
        now = time.monotonic()
        log_progress(history, y, len(evaluated), i, reference_front,
                     verbose=verbose, tag="service", word="eval",
                     wall_s=now - t_round, events=ev, track=workload)
        t_round = now

    if snap is None:
        log_round(0)

    fpool = FlowPool(flow, workload=workload,
                     max_workers=q if max_workers is None else max_workers,
                     executor=executor, cache=disk,
                     metrics=metrics, events=ev)
    if disk is not None:
        disk.bind_metrics(metrics)
    pending: list[tuple[int, int]] = []  # (ticket, pool row), ticket order
    # Proposal cadence marker: the highest ``done // every`` already
    # proposed for. Checkpointed — a resumed run must not re-propose (or
    # skip) a cadence slot the killed run already consumed.
    prop_mark = (0 if snap is None
                 else int(snap.get("prop_mark", done // pcfg.every)))
    try:
        if snap is not None:  # re-dispatch what was in flight at the kill
            for r in (int(r) for r in snap["pending"]):
                pending.append((fpool.submit(r, pool_idx[r]), r))

        while done < T or pending:
            want = min(q - len(pending), T - done - len(pending))
            if want > 0:
                key, k_fit, k_acq, k_sub = jax.random.split(key, 4)
                del k_fit  # reserved slot — keeps the schedule seed-stable
                sub = frontier_subset_rows(k_sub, N, frontier_subset)
                picks = engine.select_q(
                    k_acq, want, sub_rows=sub,
                    pending=[r for _, r in pending], fantasy=fantasy)
                for p in picks:
                    pending.append((fpool.submit(p, pool_idx[p]), p))
            results = fpool.drain(min_done=min(min_done, len(pending)),
                                  ordered=ordered)
            for t, row, y_row in results:
                engine.observe([row], y_row[None])
                evaluated.append(int(row))
                y = np.concatenate([y, np.asarray(y_row, y.dtype)[None]], 0)
                pending.remove((t, row))
                done += 1
                log_round(done)
            # Between-evaluation proposal (default off): keyed off the
            # carried key + completion count via fold_in (the split schedule
            # never advances), so an ordered run's proposals are worker-
            # timing independent. In-flight rows are never victims; runs
            # before the checkpoint so a SIGKILL resumes on the edited pool.
            if pcfg.enabled and results and done // pcfg.every > prop_mark:
                out = propose_and_replace(
                    engine, space,
                    jax.random.fold_in(key, PROPOSER_FOLD + done),
                    pool_idx, cfg=pcfg,
                    encode_cols=lambda c: transform_to_icd(
                        space, pruned.apply_pins(jnp.asarray(c)), v),
                    evaluated=[evaluated], ys=[y],
                    pending=[r for _, r in pending], stats=pstats)
                prop_mark = done // pcfg.every
                if out is not None:
                    pool_idx[out.victims] = out.new_idx
            if checkpoint_dir and results and \
                    (done % checkpoint_every == 0 or done >= T):
                ckpt = {
                    "driver": "service_tuner", "done": done,
                    "pool": pool_fp, "config": config,
                    "key": np.asarray(key), "v": np.asarray(v),
                    "evaluated": np.asarray(evaluated, np.int64), "y": y,
                    "history": history,
                    "pending": np.asarray([r for _, r in pending], np.int64),
                    "engine": engine.state_dict()}
                if pcfg.enabled:
                    ckpt["pool_live"] = np.array(pool_idx)
                    ckpt["proposer_stats"] = pstats.as_dict()
                    ckpt["prop_mark"] = int(prop_mark)
                save_snapshot(snapshot_path(checkpoint_dir, done), ckpt)
                prune_snapshots(checkpoint_dir)
                if ev is not None:
                    ev.instant("checkpoint", cat="service", track=workload,
                               done=done)
                if _kill_after is not None and done >= _kill_after:
                    os.kill(os.getpid(), signal.SIGKILL)
    finally:
        fpool.close()
        if ev is not None and _ev_owned:
            ev.close()

    front = _front(y)
    rows = np.asarray(evaluated)
    engine.stats.fold_into(metrics)
    stats = engine.stats.as_dict()
    if pcfg.enabled:
        pstats.fold_into(metrics)
        stats["proposer"] = pstats.as_dict()
    stats["service"] = {
        "pool_dispatched": fpool.dispatched,
        "pool_cache_hits": fpool.cache_hits,
        **({"disk": {"hits": disk.hits, "misses": disk.misses,
                     "puts": disk.puts}} if disk is not None else {}),
    }
    return TunerResult(
        space=pruned, v=np.asarray(v), evaluated_rows=rows, y=y,
        pareto_rows=rows[front], pareto_y=y[front], history=history,
        wall_s=time.monotonic() - t0, engine_stats=stats)
