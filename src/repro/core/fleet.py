"""Fleet runner — batched multi-scenario SoC exploration.

``soc_tuner`` (Algorithm 3) explores ONE (workload, seed) with one Python
call; sweeping the paper's protocol — several workloads × several seeds ×
several objective weightings — repeats the expensive inner round S times per
BO iteration. The fleet runner turns that outer loop inside out:

* the per-round GP fit and IMOO acquisition are executed for **all scenarios
  in one vmapped XLA program** (``fit_gp_batch`` / ``imoo_scores_batch``) —
  every scenario's training set is padded onto a fleet-wide static shape so
  the jit cache is shared across scenarios AND rounds;
* flow evaluations go through a **shared memoized cache** keyed by
  (workload, pool row): two seeds exploring ResNet-50 never pay twice for the
  same design point, and ICD trials of one scenario seed the GP of another
  for free;
* cache misses pending for *different* workloads are fused into a single
  dispatch of ``soc_metrics_multi`` (the surrogate broadcasts over designs ×
  layers; the fleet vmaps the workload axis on top).

Per-scenario math is computation-for-computation identical to ``soc_tuner``:
a fleet of one reproduces the sequential trajectory on the same seed (see
``tests/test_fleet.py``).

Usage::

    from repro.core import FleetScenario, fleet_tuner, make_space
    space = make_space()
    pool = np.asarray(space.sample(jax.random.PRNGKey(0), 1000))
    scenarios = [FleetScenario("resnet50", seed=0),
                 FleetScenario("resnet50", seed=1),
                 FleetScenario("transformer", seed=0,
                               weights=(2.0, 1.0, 1.0))]   # latency-hungry
    fr = fleet_tuner(space, pool, scenarios, T=15, n=20, b=12)
    for sc, res in zip(fr.scenarios, fr.results):
        print(sc.label, res.pareto_y)
    print(fr.cache.summary())
"""
from __future__ import annotations

import dataclasses
import time
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs import log_progress

from .engine import BatchedBOEngine
from .icd import icd_from_data
from .pareto import pareto_mask
from .propose import (PROPOSER_FOLD, ProposerConfig, ProposerStats,
                      propose_and_replace)
from .sampling import soc_init, transform_to_icd
from .space import DesignSpace
from .tuner import (TunerResult, frontier_subset_rows, icd_trial_rows,
                    merge_trial_evals)

__all__ = ["FleetScenario", "FleetResult", "FlowEvalCache", "fleet_tuner",
           "fleet_prologue"]


@dataclasses.dataclass(frozen=True)
class FleetScenario:
    """One exploration scenario: a workload, an RNG seed, and an optional
    per-objective acquisition weighting (latency, power, area)."""

    workload: str
    seed: int = 0
    weights: tuple[float, float, float] = (1.0, 1.0, 1.0)

    @property
    def label(self) -> str:
        w = ""
        if tuple(self.weights) != (1.0, 1.0, 1.0):
            w = ":w" + "x".join(f"{x:g}" for x in self.weights)
        return f"{self.workload}:s{self.seed}{w}"


class FlowEvalCache:
    """Memoized flow evaluations shared across a fleet.

    Keyed by ``(workload, pool row)``; misses are batched — per flush, one
    XLA dispatch when a single workload is pending, one fused
    ``soc_metrics_multi`` dispatch when several are. ``hits``/``misses``
    count *requests*, ``evaluated`` counts design points actually pushed
    through the surrogate (== stored entries), ``flow_calls`` counts
    dispatches.

    ``disk`` (a :class:`repro.service.flowcache.FlowDiskCache` or a root
    path) backs the in-memory store with the content-addressed on-disk
    cache: in-memory misses consult the disk before any dispatch
    (``disk_hits`` counts how many flushes resolved that way) and every
    computed result is written back atomically — so concurrent fleets,
    service runs and restarts share one evaluation corpus.

    ``flow_factory`` (``workload -> flow callable``, optional) replaces the
    built-in surrogate dispatch: misses are evaluated by calling the
    workload's flow on the raw design-index rows instead of
    ``soc_metrics``/``soc_metrics_multi`` directly. This is how a *real*
    (or mocked-latency) flow is plugged under ``fleet_tuner`` — e.g. the
    synchronous baseline of the fleet-service benchmark. The default
    (``None``) keeps the historical fused dispatch bit-for-bit.
    """

    def __init__(self, space: DesignSpace, pool_idx: np.ndarray,
                 workloads: Sequence[str], disk=None, flow_factory=None):
        from repro.soc.workloads import get_workload

        self.space = space
        self.pool_idx = np.asarray(pool_idx)
        self.layers = {w: np.asarray(get_workload(w), np.float64)
                       for w in dict.fromkeys(workloads)}
        self._store: dict[str, dict[int, np.ndarray]] = {
            w: {} for w in self.layers}
        if disk is not None and not hasattr(disk, "get"):
            from repro.service.flowcache import FlowDiskCache

            disk = FlowDiskCache(disk)
        self.disk = disk
        self._flows = (None if flow_factory is None
                       else {w: flow_factory(w) for w in self.layers})
        self.hits = 0
        self.misses = 0
        self.disk_hits = 0
        self.flow_calls = 0
        self.evaluated = 0
        self.peek_hits = 0
        self.peek_misses = 0
        self.invalidated = 0

    # ---------------------------------------------------- pool-edit support
    def invalidate_rows(self, rows) -> None:
        """Drop in-memory entries for pool rows whose *design* changed (the
        between-round proposer replaced those columns) — the memo is keyed
        by row index, so a stale hit would return the old design's metrics.
        The on-disk cache is content-addressed (keyed by the design index
        vector itself) and needs no invalidation; ``self.pool_idx`` is a
        live view of the driver's pool, so post-edit misses hash the new
        content automatically."""
        for r in np.asarray(rows).reshape(-1):
            r = int(r)
            for store in self._store.values():
                if store.pop(r, None) is not None:
                    self.invalidated += 1

    # ------------------------------------------------------- external feed
    def peek(self, workload: str, row) -> np.ndarray | None:
        """In-memory-only lookup of one pool row (no disk IO, no dispatch).
        The fleet service consults this before submitting a pick to its
        worker pool. Counted separately (``peek_hits``/``peek_misses``) —
        a probe-before-dispatch is not a flush-level cache miss, so the
        shared ``hits``/``misses`` stats keep measuring flush behavior."""
        y = self._store[workload].get(int(row))
        if y is None:
            self.peek_misses += 1
        else:
            self.peek_hits += 1
        return y

    def store(self, workload: str, row, y) -> None:
        """Record an externally evaluated result (the fleet service feeds
        worker-pool completions back so later picks of ANY scenario hit)."""
        if int(row) not in self._store[workload]:
            self.evaluated += 1
        self._store[workload][int(row)] = np.asarray(y)

    @property
    def requests(self) -> int:
        return self.hits + self.misses

    @property
    def hit_rate(self) -> float:
        return self.hits / max(self.requests, 1)

    def summary(self) -> str:
        disk = (f", {self.disk_hits} disk hits" if self.disk is not None
                else "")
        return (f"cache: {self.requests} requests, {self.hits} hits "
                f"({100.0 * self.hit_rate:.1f}%){disk}, {self.evaluated} "
                f"designs evaluated in {self.flow_calls} flow dispatches")

    # ------------------------------------------------------------------ eval
    def evaluate_many(self, reqs: list[tuple[str, np.ndarray]]
                      ) -> list[np.ndarray]:
        """Resolve ``[(workload, rows), ...]`` -> ``[y [len(rows), 3], ...]``.

        All cache misses across all requests are evaluated in one flush
        before any result is assembled."""
        pending: dict[str, list[int]] = {}
        for wl, rows in reqs:
            store = self._store[wl]
            seen = pending.setdefault(wl, [])
            for r in np.asarray(rows).reshape(-1):
                r = int(r)
                if r in store or r in seen:
                    self.hits += 1
                else:
                    seen.append(r)
                    self.misses += 1
        self._flush({w: rows for w, rows in pending.items() if rows})
        return [np.stack([self._store[wl][int(r)]
                          for r in np.asarray(rows).reshape(-1)])
                for wl, rows in reqs]

    def evaluate(self, workload: str, rows: np.ndarray) -> np.ndarray:
        return self.evaluate_many([(workload, rows)])[0]

    def _flush(self, pending: dict[str, list[int]]) -> None:
        from repro.soc.model import soc_metrics, soc_metrics_multi
        from repro.soc.workloads import pad_workloads

        if self.disk is not None and pending:
            # Resolve what the shared on-disk corpus already knows before
            # paying any dispatch; leftovers are written back after compute.
            for wl in list(pending):
                left = []
                for r in pending[wl]:
                    y = self.disk.get(wl, self.pool_idx[r])
                    if y is None:
                        left.append(r)
                    else:
                        self._store[wl][r] = np.asarray(y)
                        self.disk_hits += 1
                if left:
                    pending[wl] = left
                else:
                    del pending[wl]
        if not pending:
            return
        if self._flows is not None:
            # Injected flows: one call per pending workload (the flow owns
            # its own batching/latency — this is the real-flow seam).
            for wl, rows in pending.items():
                self.flow_calls += 1
                self.evaluated += len(rows)
                y = np.atleast_2d(np.asarray(
                    self._flows[wl](self.pool_idx[np.asarray(rows)])))
                for r, yr in zip(rows, y):
                    self._store[wl][r] = yr
                    if self.disk is not None:
                        self.disk.put(wl, self.pool_idx[r], yr)
            return
        self.flow_calls += 1
        self.evaluated += sum(len(r) for r in pending.values())
        if len(pending) == 1:
            # Single-workload flush: the exact batch a sequential ``VLSIFlow``
            # call would issue — bit-identical metrics for a fleet of one.
            (wl, rows), = pending.items()
            vals = self.space.values(self.pool_idx[np.asarray(rows)])
            y = np.asarray(soc_metrics(jnp.asarray(vals, jnp.float32),
                                       jnp.asarray(self.layers[wl], jnp.float32)))
            for r, yr in zip(rows, y):
                self._store[wl][r] = yr
                if self.disk is not None:
                    self.disk.put(wl, self.pool_idx[r], yr)
            return
        # Fused path: pad rows to a common count and layers to a common depth,
        # then one vmapped dispatch covers every pending workload.
        names = list(pending)
        rmax = max(len(pending[w]) for w in names)
        vals = np.stack([
            self.space.values(self.pool_idx[np.asarray(
                pending[w] + pending[w][:1] * (rmax - len(pending[w])))])
            for w in names])
        layers, mask = pad_workloads([self.layers[w] for w in names])
        y = np.asarray(soc_metrics_multi(jnp.asarray(vals, jnp.float32),
                                         jnp.asarray(layers, jnp.float32),
                                         jnp.asarray(mask, jnp.float32)))
        for wi, w in enumerate(names):
            for ri, r in enumerate(pending[w]):
                self._store[w][r] = y[wi, ri]
                if self.disk is not None:
                    self.disk.put(w, self.pool_idx[r], y[wi, ri])


@dataclasses.dataclass
class FleetResult:
    scenarios: list[FleetScenario]
    results: list[TunerResult]      # per scenario, same layout as soc_tuner's
    cache: FlowEvalCache
    wall_s: float

    def final_adrs(self) -> dict[str, float]:
        """label -> last-round ADRS (scenarios run with a reference front)."""
        return {sc.label: res.history[-1]["adrs"]
                for sc, res in zip(self.scenarios, self.results)
                if "adrs" in res.history[-1]}


@dataclasses.dataclass
class _ScenarioState:
    """Host-side bookkeeping for one scenario between batched rounds."""

    key: jax.Array
    v: np.ndarray
    pruned: DesignSpace
    pool_icd: jnp.ndarray            # [N, d]
    evaluated: list[int]
    y: np.ndarray                    # [k, 3]
    weights: jnp.ndarray | None
    history: list[dict]


def _log_round(st: _ScenarioState, i: int, label: str,
               reference_front: np.ndarray | None, verbose: bool,
               tag: str = "fleet", wall_s: float | None = None,
               events=None) -> None:
    log_progress(st.history, st.y, len(st.evaluated), i, reference_front,
                 verbose=verbose, tag=tag, label=label, wall_s=wall_s,
                 events=events)


def fleet_prologue(space: DesignSpace, pool_idx: np.ndarray,
                   scenarios: Sequence[FleetScenario], cache: FlowEvalCache,
                   *, n: int, mu: float, b: int, v_th: float,
                   reuse_icd_trials: bool, reference_fronts: dict,
                   verbose: bool, snap: dict | None = None,
                   tag: str = "fleet") -> "list[_ScenarioState]":
    """Alg. 3 lines 1-4 for every scenario: ICD trials (one fused flush),
    importance + pruning + TED init, seed evaluations. The key schedule
    matches ``soc_tuner`` exactly, so a fleet-of-one consumes the PRNG
    stream identically to the sequential driver. On resume (``snap``) the
    flow-dependent pieces are restored from the snapshot and only the
    deterministic ``soc_init`` transform is replayed. Shared by
    :func:`fleet_tuner` and the async fleet service
    (``repro.service.fleet_runner``) — the two drivers' prologues can never
    drift apart."""
    states: list[_ScenarioState] = []
    if snap is None:
        trial_sets: list[np.ndarray] = []
        for sc in scenarios:
            trial_rows, key = icd_trial_rows(jax.random.PRNGKey(sc.seed),
                                             pool_idx.shape[0], n)
            trial_sets.append(trial_rows)
            states.append(_ScenarioState(
                key=key, v=np.zeros(space.d), pruned=space,
                pool_icd=jnp.zeros(()), evaluated=[], y=np.zeros((0, 3)),
                weights=(None if tuple(sc.weights) == (1.0, 1.0, 1.0)
                         else jnp.asarray(sc.weights, jnp.float32)),
                history=[]))
        trial_ys = cache.evaluate_many(
            [(sc.workload, rows) for sc, rows in zip(scenarios, trial_sets)])

        init_reqs: list[tuple[str, np.ndarray]] = []
        for sc, st, trial_rows, trial_y in zip(scenarios, states, trial_sets,
                                               trial_ys):
            st.v = icd_from_data(space, pool_idx[trial_rows], trial_y)
            init_rows, st.pruned, pool_icd = soc_init(
                space, pool_idx, st.v, v_th=v_th, b=b, mu=mu)
            st.pool_icd = jnp.asarray(pool_icd, jnp.float32)
            st.evaluated = list(dict.fromkeys(int(r) for r in init_rows))
            init_reqs.append((sc.workload, np.asarray(st.evaluated)))
        init_ys = cache.evaluate_many(init_reqs)

        for sc, st, trial_rows, trial_y, init_y in zip(
                scenarios, states, trial_sets, trial_ys, init_ys):
            st.evaluated, st.y = merge_trial_evals(
                st.evaluated, init_y, trial_rows, trial_y, reuse_icd_trials)
            _log_round(st, 0, sc.label, reference_fronts.get(sc.workload),
                       verbose, tag)
    else:
        for si, sc in enumerate(scenarios):
            v = np.asarray(snap["vs"][str(si)])
            _, pruned, pool_icd = soc_init(space, pool_idx, v, v_th=v_th,
                                           b=b, mu=mu)
            states.append(_ScenarioState(
                key=jnp.asarray(snap["keys"][si]), v=v, pruned=pruned,
                pool_icd=jnp.asarray(pool_icd, jnp.float32),
                evaluated=[int(r) for r in snap["evaluated"][str(si)]],
                y=np.asarray(snap["ys"][str(si)]),
                weights=(None if tuple(sc.weights) == (1.0, 1.0, 1.0)
                         else jnp.asarray(sc.weights, jnp.float32)),
                history=list(snap["histories"][str(si)])))
    return states


def fleet_tuner(
    space: DesignSpace,
    pool_idx: np.ndarray,
    scenarios: Sequence[FleetScenario],
    *,
    T: int = 40,
    n: int = 30,
    mu: float = 0.1,
    b: int = 20,
    v_th: float = 0.07,
    s_frontiers: int = 10,
    frontier_subset: int = 512,
    gp_steps: int = 150,
    reference_fronts: dict[str, np.ndarray] | None = None,
    reuse_icd_trials: bool = True,
    incremental: bool = False,
    warm_start: bool | None = None,
    warm_steps: int | None = None,
    drift_tol: float = 1.0,
    pool_chunk: int | str | None = None,
    mesh=None,
    mesh_axis: str | None = None,
    disk_cache=None,
    flow_factory=None,
    checkpoint_dir: str | None = None,
    checkpoint_every: int = 1,
    resume: bool = False,
    proposer=None,
    verbose: bool = False,
) -> FleetResult:
    """Explore every scenario of a fleet over the SAME candidate pool.

    Hyperparameters mirror :func:`repro.core.soc_tuner` and apply to every
    scenario; ``reference_fronts`` maps workload name -> true Pareto front
    for per-round ADRS logging. Returns one ``TunerResult`` per scenario plus
    fleet-level cache statistics.

    The batched per-round surrogate work runs on one
    :class:`repro.core.engine.BatchedBOEngine` (engine state carries a
    leading scenario axis). ``incremental=False`` (the fidelity default)
    reproduces the historical batched rounds exactly — a fleet of one still
    matches sequential ``soc_tuner`` bit-for-bit; ``incremental=True``
    enables warm-started fits, rank-k Cholesky block updates, cached pool
    covariances and device-side selection across the whole fleet, with the
    refactor-vs-update decision taken fleet-wide.

    ``pool_chunk`` (int | ``"auto"``) streams the engine's O(N) pool state
    in column chunks (huge-pool regime — identical selections at any chunk
    size); ``mesh`` (a ``jax.sharding.Mesh``) shards the scenario axis over
    devices with ``shard_map`` — one scenario group per device, the
    per-round host sync fused into the fleet-wide drift max plus one gather
    of the [S] picks. Both require ``incremental=True``; ``S`` must divide
    evenly over the mesh axis. See ``docs/scaling.md``.

    ``disk_cache`` (path or ``repro.service.flowcache.FlowDiskCache``) backs
    the in-memory evaluation cache with the content-addressed on-disk store
    shared across fleets, service runs and restarts. ``flow_factory``
    (``workload -> flow``) plugs a real/mocked flow under the evaluation
    cache instead of the built-in surrogate dispatch (see
    :class:`FlowEvalCache`) — the asynchronous twin of this driver is
    ``repro.service.fleet_service``, which overlaps those flow calls on a
    concurrent worker pool. ``checkpoint_dir`` /
    ``checkpoint_every`` / ``resume`` snapshot the full fleet state (batched
    engine, per-scenario keys/history) each round and continue a killed run
    bit-exactly — the resumed prologue is rebuilt from the checkpointed
    importance vectors without re-paying any flow evaluation.

    ``proposer`` (None | bool | dict | :class:`ProposerConfig`; default OFF,
    requires ``incremental=True``, incompatible with ``mesh``) enables the
    between-round perturbation proposer fleet-wide: parents are the union
    of every scenario's Pareto front, victims the columns no scenario still
    values (max-over-scenarios ``pool_scores``). Row-keyed cache entries of
    replaced columns are invalidated; checkpoints carry the live pool.
    """
    t0 = time.monotonic()
    scenarios = list(scenarios)
    pool_idx = np.asarray(pool_idx)
    pcfg = ProposerConfig.from_arg(proposer)
    pstats = ProposerStats()
    if pcfg.enabled:
        if not incremental:
            raise ValueError(
                "proposer requires incremental=True: victim scoring runs on "
                "the incremental engine's cached round state (pool_scores)")
        if mesh is not None:
            raise ValueError(
                "proposer is incompatible with mesh sharding: pool edits "
                "rewrite host-gathered V chunks (run unsharded, or propose "
                "offline between sharded runs)")
        # Private copy — the proposer edits it; the cache below aliases the
        # SAME array so its content-addressed disk keys and flow dispatches
        # always see the live designs.
        pool_idx = np.array(pool_idx)
    N = pool_idx.shape[0]
    reference_fronts = reference_fronts or {}
    cache = FlowEvalCache(space, pool_idx, [sc.workload for sc in scenarios],
                          disk=disk_cache, flow_factory=flow_factory)

    config = {"n": int(n), "b": int(b), "mu": float(mu),
              "v_th": float(v_th), "gp_steps": int(gp_steps),
              "s_frontiers": int(s_frontiers),
              "frontier_subset": int(frontier_subset),
              "incremental": bool(incremental), "pool_chunk": pool_chunk,
              "warm_start": warm_start, "warm_steps": warm_steps,
              "drift_tol": float(drift_tol),
              "reuse_icd_trials": bool(reuse_icd_trials),
              # exact per-scenario parameters: labels round-trip weights
              # through %g formatting, which can collide at >6 significant
              # digits — the guard must compare the real values
              "scenario_params": [
                  [sc.workload, int(sc.seed), [float(w) for w in sc.weights]]
                  for sc in scenarios]}
    if pcfg.enabled:
        # Joins the trajectory guard only when ON — proposer-less
        # checkpoints written before this knob existed keep resuming.
        config["proposer"] = pcfg.as_dict()
    from repro.core.tuner import _pool_fingerprint

    # Fingerprint of the pool AS PASSED — the proposer edits pool_idx, but
    # a resuming caller passes the original pool, so the guard pins that.
    pool_fp = _pool_fingerprint(pool_idx)
    snap = None
    if resume and checkpoint_dir:
        from repro.service.checkpoint import load_latest_validated

        snap = load_latest_validated(
            checkpoint_dir, driver="fleet_tuner", pool=pool_fp, config=config)
        if snap is not None and \
                snap["scenarios"] != [sc.label for sc in scenarios]:
            raise ValueError(f"checkpoint in {checkpoint_dir} was taken for "
                             f"scenarios {snap['scenarios']} — resume "
                             "requires the identical fleet")
        if snap is not None and pcfg.enabled and "pool_live" in snap:
            # In-place: the cache aliases this array. Evaluated rows are
            # immutable, so every recorded pick still denotes its design.
            np.copyto(pool_idx, np.asarray(snap["pool_live"]))
            pstats = ProposerStats.from_dict(snap["proposer_stats"])

    # ---- Alg. 3 lines 1-4 per scenario (shared with the fleet service).
    states = fleet_prologue(space, pool_idx, scenarios, cache, n=n, mu=mu,
                            b=b, v_th=v_th, reuse_icd_trials=reuse_icd_trials,
                            reference_fronts=reference_fronts,
                            verbose=verbose, snap=snap)

    pool_icd_stack = jnp.stack([st.pool_icd for st in states])  # [S, N, d]
    any_weights = any(st.weights is not None for st in states)
    weights = (jnp.stack([
        st.weights if st.weights is not None else jnp.ones((3,))
        for st in states]) if any_weights else None)

    # ---- Alg. 3 lines 5-10: the BO loop, batched across scenarios on one
    # persistent engine (the engine negates targets and owns the
    # never-re-evaluate mask + per-scenario argmax).
    engine = BatchedBOEngine(pool_icd_stack, incremental=incremental,
                             warm_start=warm_start, gp_steps=gp_steps,
                             warm_steps=warm_steps, drift_tol=drift_tol,
                             s_frontiers=s_frontiers, weights=weights,
                             pool_chunk=pool_chunk, mesh=mesh,
                             mesh_axis=mesh_axis)
    if snap is None:
        engine.observe([st.evaluated for st in states],
                       [st.y for st in states])
    else:
        engine.load_state_dict(snap["engine"])

    def save_checkpoint(round_i: int) -> None:
        from repro.service.checkpoint import (prune_snapshots, save_snapshot,
                                              snapshot_path)

        d = {
            "driver": "fleet_tuner", "round": round_i,
            "pool": pool_fp, "config": config,
            "scenarios": [sc.label for sc in scenarios],
            "keys": np.stack([np.asarray(st.key) for st in states]),
            "vs": {str(si): np.asarray(st.v)
                   for si, st in enumerate(states)},
            "evaluated": {str(si): np.asarray(st.evaluated, np.int64)
                          for si, st in enumerate(states)},
            "ys": {str(si): st.y for si, st in enumerate(states)},
            "histories": {str(si): st.history
                          for si, st in enumerate(states)},
            "engine": engine.state_dict()}
        if pcfg.enabled:
            d["pool_live"] = np.array(pool_idx)
            d["proposer_stats"] = pstats.as_dict()
        save_snapshot(snapshot_path(checkpoint_dir, round_i), d)
        prune_snapshots(checkpoint_dir)

    start_round = 0 if snap is None else int(snap["round"])
    for it in range(start_round, T):
        subs, keys_acq = [], []
        for st in states:
            st.key, k_fit, k_acq, k_sub = jax.random.split(st.key, 4)
            del k_fit  # reserved slot — keeps the schedule aligned w/ tuner
            subs.append(frontier_subset_rows(k_sub, N, frontier_subset))
            keys_acq.append(k_acq)

        # Line 7-8 per scenario: one batched engine round picks every
        # scenario's argmax; evaluate all picks in ONE fused flush
        # (cross-scenario batching + cache dedup).
        picks = [int(p) for p in engine.select(
            jnp.stack(keys_acq),
            sub_rows=None if subs[0] is None else np.stack(subs))]
        pick_ys = cache.evaluate_many(
            [(sc.workload, np.asarray([p]))
             for sc, p in zip(scenarios, picks)])
        engine.observe([[p] for p in picks], pick_ys)
        for sc, st, p, y_new in zip(scenarios, states, picks, pick_ys):
            st.evaluated.append(p)
            st.y = np.concatenate([st.y, y_new], axis=0)
            _log_round(st, it + 1, sc.label,
                       reference_fronts.get(sc.workload), verbose)
        # Between-round proposal (default off), fleet-wide: parents are the
        # union of every scenario's front, a column survives if ANY scenario
        # still values it. Keyed off scenario 0's carried key via fold_in —
        # no scenario's split schedule advances, so proposer-off trajectories
        # stay byte-identical. Runs before the checkpoint so a killed run
        # resumes on exactly the pool the next round would have seen.
        if pcfg.enabled and (it + 1) % pcfg.every == 0:
            out = propose_and_replace(
                engine, space,
                jax.random.fold_in(states[0].key, PROPOSER_FOLD + it),
                pool_idx, cfg=pcfg,
                encode_cols=lambda c: jnp.stack([
                    transform_to_icd(space,
                                     st.pruned.apply_pins(jnp.asarray(c)),
                                     st.v)
                    for st in states]),
                evaluated=[st.evaluated for st in states],
                ys=[st.y for st in states], stats=pstats)
            if out is not None:
                pool_idx[out.victims] = out.new_idx   # cache aliases this
                cache.invalidate_rows(out.victims)
        if checkpoint_dir and (it + 1) % checkpoint_every == 0:
            save_checkpoint(it + 1)

    # ---- package per-scenario results in soc_tuner's own layout.
    wall = time.monotonic() - t0
    results = []
    for st in states:
        rows = np.asarray(st.evaluated)
        front = np.asarray(pareto_mask(jnp.asarray(st.y.astype(np.float64))))
        stats_d = engine.stats.as_dict()
        if pcfg.enabled:
            stats_d["proposer"] = pstats.as_dict()
        results.append(TunerResult(
            space=st.pruned, v=np.asarray(st.v), evaluated_rows=rows, y=st.y,
            pareto_rows=rows[front], pareto_y=st.y[front], history=st.history,
            wall_s=wall, engine_stats=stats_d))
    return FleetResult(scenarios=scenarios, results=results, cache=cache,
                       wall_s=wall)
