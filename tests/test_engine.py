"""BOEngine equivalence: exact path reproduces the seed loop bit-for-bit,
rank-k Cholesky block updates match full refactorization, the batched engine
drives the fleet, and the warm-start plumbing reaches fit_gp."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (BOEngine, FleetScenario, fleet_tuner, pareto_front,
                        soc_tuner)
from repro.core.acquisition import imoo_scores
from repro.core.gp import fit_gp
from repro.core.icd import icd_from_data
from repro.core.sampling import soc_init
from repro.core.tuner import (frontier_subset_rows, icd_trial_rows,
                              merge_trial_evals)
from repro.soc import VLSIFlow


def _seed_loop(space, pool, *, T, n, b, gp_steps, key):
    """The pre-engine Algorithm 3 loop, verbatim: the fidelity reference for
    ``BOEngine(incremental=False)``."""
    flow = VLSIFlow(space, "resnet50")
    N = pool.shape[0]
    trial_rows, key = icd_trial_rows(key, N, n)
    trial_y = np.asarray(flow(pool[trial_rows]))
    v = icd_from_data(space, pool[trial_rows], trial_y)
    init_rows, _, pool_icd = soc_init(space, pool, v, v_th=0.07, b=b, mu=0.1)
    pool_icd = jnp.asarray(pool_icd, jnp.float32)
    evaluated = list(dict.fromkeys(int(r) for r in init_rows))
    y_init = np.asarray(flow(pool[np.asarray(evaluated)]))
    evaluated, y = merge_trial_evals(evaluated, y_init, trial_rows, trial_y,
                                     True)
    for _ in range(T):
        key, _k_fit, k_acq, k_sub = jax.random.split(key, 4)
        rows = np.asarray(evaluated)
        state = fit_gp(pool_icd[rows], jnp.asarray(-y, jnp.float32),
                       steps=gp_steps)
        sub = frontier_subset_rows(k_sub, N, 512)
        fc = pool_icd if sub is None else pool_icd[sub]
        scores = np.array(imoo_scores(state, pool_icd, k_acq, s=10,
                                      frontier_cand=fc))
        scores[rows] = -np.inf
        nxt = int(np.argmax(scores))
        y = np.concatenate([y, np.asarray(flow(pool[nxt][None, :]))], axis=0)
        evaluated.append(nxt)
    return np.asarray(evaluated), y


def _engine_driver(space, pool, *, T, n, b, gp_steps, seed, **engine_kw):
    """soc_tuner with a fresh flow (shared helper for the equivalence runs)."""
    return soc_tuner(space, pool, VLSIFlow(space, "resnet50"), T=T, n=n, b=b,
                     gp_steps=gp_steps, key=jax.random.PRNGKey(seed),
                     **engine_kw)


def test_exact_engine_reproduces_seed_trajectory(space, small_pool):
    """(b) BOEngine(incremental=False) == the historical loop, bit-for-bit."""
    kw = dict(T=6, n=12, b=8, gp_steps=40)
    rows_ref, y_ref = _seed_loop(space, small_pool, key=jax.random.PRNGKey(7),
                                 **kw)
    res = _engine_driver(space, small_pool, seed=7, incremental=False, **kw)
    np.testing.assert_array_equal(rows_ref, res.evaluated_rows)
    np.testing.assert_array_equal(y_ref, res.y)
    assert res.engine_stats["refactors"] == 0  # exact path never factors


def test_incremental_chol_matches_refactor(space, small_pool):
    """(a) the rank-k block-updated Cholesky equals a full refactorization
    under the same (frozen) hyperparameters, every round of a 10-round run —
    and the update path is actually exercised."""
    flow = VLSIFlow(space, "resnet50")
    pool_y = flow(small_pool)
    trial_rows, key = icd_trial_rows(jax.random.PRNGKey(5),
                                     small_pool.shape[0], 12)
    v = icd_from_data(space, small_pool[trial_rows], pool_y[trial_rows])
    _, _, pool_icd = soc_init(space, small_pool, v, v_th=0.07, b=8, mu=0.1)
    eng = BOEngine(jnp.asarray(pool_icd, jnp.float32), incremental=True,
                   gp_steps=40, warm_steps=5, drift_tol=5.0)
    rows0 = [int(r) for r in trial_rows]
    eng.observe(rows0, np.asarray(pool_y)[np.asarray(rows0)])
    for _ in range(10):
        key, k_acq = jax.random.split(key)
        nxt = eng.select(k_acq)
        assert eng.refactor_residual() < 5e-4
        eng.observe([nxt], np.asarray(flow(small_pool[nxt][None, :])))
    assert eng.stats.block_updates > 0
    assert eng.stats.refactors >= 1  # at least the cold start / bucket growth
    assert eng.stats.rounds == 10


def test_incremental_tuner_matches_exact_quality(space, small_pool):
    """The incremental path explores sanely: a valid non-dominated front over
    its own evaluations and a final ADRS in the same regime as the exact
    path's (the trajectories legitimately differ — warm-started fits)."""
    flow = VLSIFlow(space, "resnet50")
    ref = pareto_front(flow(small_pool))
    kw = dict(T=8, n=12, b=8, gp_steps=40)
    rx = soc_tuner(space, small_pool, VLSIFlow(space, "resnet50"),
                   reference_front=ref, key=jax.random.PRNGKey(1),
                   incremental=False, **kw)
    ri = soc_tuner(space, small_pool, VLSIFlow(space, "resnet50"),
                   reference_front=ref, key=jax.random.PRNGKey(1),
                   incremental=True, **kw)
    from repro.core import pareto_mask
    assert bool(pareto_mask(jnp.asarray(ri.pareto_y)).all())
    assert ri.history[-1]["adrs"] <= ri.history[0]["adrs"] + 1e-9
    assert ri.history[-1]["adrs"] <= rx.history[0]["adrs"] + 1e-9
    assert ri.engine_stats["rounds"] == kw["T"]
    assert (ri.engine_stats["refactors"]
            + ri.engine_stats["block_updates"]) == kw["T"]


def test_warm_start_plumbs_into_fit_gp(space, small_pool):
    """The previously dead ``params`` arg of fit_gp is reachable from
    soc_tuner: warm-started cold-structure runs stay valid and (with a short
    step budget) leave a different trajectory than cold restarts."""
    kw = dict(T=5, n=12, b=8, gp_steps=20)
    cold = _engine_driver(space, small_pool, seed=2, incremental=False,
                          warm_start=False, **kw)
    warm = _engine_driver(space, small_pool, seed=2, incremental=False,
                          warm_start=True, **kw)
    assert len(warm.history) == len(cold.history)
    assert np.isfinite(warm.y).all()
    # identical until the 2nd BO pick (round 1 fits from the same start)
    n0 = len(cold.evaluated_rows) - kw["T"]
    np.testing.assert_array_equal(cold.evaluated_rows[:n0 + 1],
                                  warm.evaluated_rows[:n0 + 1])
    assert not np.array_equal(cold.evaluated_rows, warm.evaluated_rows)


def test_fleet_incremental_runs_and_shares_cache(space, small_pool):
    """BatchedBOEngine drives the fleet: two seeds explore with rank-k
    updates + fleet-wide refactor policy, cache accounting stays sound."""
    fr = fleet_tuner(space, small_pool,
                     [FleetScenario("resnet50", seed=0),
                      FleetScenario("resnet50", seed=1)],
                     T=4, n=10, b=6, gp_steps=30, incremental=True)
    assert len(fr.results) == 2
    for res in fr.results:
        assert np.isfinite(res.y).all()
        assert len(res.history) == 5
        assert res.engine_stats["rounds"] == 4
    assert fr.cache.misses == fr.cache.evaluated
    st = fr.results[0].engine_stats
    assert st["refactors"] + st["block_updates"] == 4


def test_exact_round_is_one_dispatch():
    """An exact round counts one program launch and no factorization
    decision: refactors and block updates are the incremental policy's."""
    rng = np.random.default_rng(0)
    pool = rng.normal(size=(40, 5)).astype(np.float32)
    eng = BOEngine(pool, incremental=False, gp_steps=5, s_frontiers=4)
    eng.observe(list(range(6)), rng.normal(size=(6, 3)))
    for r in range(2):
        before = eng.stats.as_dict()
        nxt = eng.select(jax.random.PRNGKey(r))
        after = eng.stats.as_dict()
        assert nxt not in eng._rows and 0 <= nxt < 40
        assert after["rounds"] - before["rounds"] == 1
        assert after["dispatches"] - before["dispatches"] == 1
        assert after["refactors"] == after["block_updates"] == 0
        eng.observe([nxt], rng.normal(size=(1, 3)))


def test_exact_round_runs_no_program_per_training_size(monkeypatch):
    """The exact round launches ``_round_seq`` once, at the static s0=0,
    and a round at a new training size inside the same 8-row bucket traces
    nothing: no eager program is shaped by the training-set size."""
    import repro.core.engine as engine_mod

    rng = np.random.default_rng(1)
    pool = rng.normal(size=(53, 7)).astype(np.float32)  # shapes of its own
    eng = BOEngine(pool, incremental=False, gp_steps=3, s_frontiers=3)
    eng.observe(list(range(9)), rng.normal(size=(9, 2)))
    nxt = eng.select(jax.random.PRNGKey(0))          # n=9: compiles P=16
    eng.observe([nxt], rng.normal(size=(1, 2)))

    launches, traces = [], []
    real = engine_mod._round_seq

    def counted(*args, **kw):
        launches.append(kw)
        return real(*args, **kw)

    def listener(name, secs, **kw):
        if name == "/jax/core/compile/jaxpr_trace_duration":
            traces.append(name)

    monkeypatch.setattr(engine_mod, "_round_seq", counted)
    jax.monitoring.register_event_duration_secs_listener(listener)
    try:
        eng.select(jax.random.PRNGKey(1))            # n=10: same bucket
    finally:
        jax.monitoring.unregister_event_duration_listener(listener)
    assert [(k["s0"], k["select"]) for k in launches] == [(0, True)]
    assert traces == []


def test_engine_padding_matches_pad_training():
    """The engine's device-side padding (row indices + in-dispatch +10 shift)
    reproduces gp.pad_training exactly — the block-update prefix assumption
    and fleet-of-one parity both lean on this convention staying in sync."""
    from repro.core.gp import pad_training

    rng = np.random.default_rng(0)
    pool = jnp.asarray(rng.normal(size=(20, 4)), jnp.float32)
    rows = [3, 11, 7, 19, 0]
    y = rng.normal(size=(5, 3)).astype(np.float32)
    P = 8
    rows_pad, y_pad, mask = BOEngine._padded_batch(rows, y, P)
    x_engine = np.asarray(pool)[rows_pad] + 10.0 * mask[:, None]
    x_ref, y_ref, mask_ref = pad_training(
        pool[np.asarray(rows)], jnp.asarray(-y, jnp.float32), P)
    np.testing.assert_allclose(x_engine, np.asarray(x_ref), rtol=0, atol=0)
    np.testing.assert_allclose(y_pad, np.asarray(y_ref), rtol=0, atol=0)
    np.testing.assert_array_equal(mask, np.asarray(mask_ref))


def test_merge_trial_evals_dedup_and_alignment():
    """Bookkeeping fix: one membership set, order preserved, y rows aligned."""
    evaluated = [3, 7]
    y_init = np.arange(2 * 3, dtype=float).reshape(2, 3)
    trial_rows = np.asarray([7, 1, 3, 9])
    trial_y = 100 + np.arange(4 * 3, dtype=float).reshape(4, 3)
    ev, y = merge_trial_evals(evaluated, y_init, trial_rows, trial_y, True)
    assert ev == [3, 7, 1, 9]                      # fresh rows in trial order
    np.testing.assert_array_equal(y[:2], y_init)
    np.testing.assert_array_equal(y[2], trial_y[1])  # row 1 -> trial idx 1
    np.testing.assert_array_equal(y[3], trial_y[3])  # row 9 -> trial idx 3
    # disabled reuse: untouched
    ev2, y2 = merge_trial_evals([3, 7], y_init, trial_rows, trial_y, False)
    assert ev2 == [3, 7] and y2.shape == (2, 3)


def test_engine_stats_dict_roundtrip_compat():
    """from_dict tolerates older snapshots (missing keys, or the retired
    ``stage_wall_s`` breakdown), newer snapshots with unknown keys, and
    round-trips through as_dict."""
    from repro.core.engine import EngineStats

    # forward compat: an older checkpoint dict loads with defaults
    old = {"rounds": 4, "refactors": 2, "block_updates": 2, "dispatches": 9,
           "fantasy_steps": 0, "frontier_resamples": 1, "last_drift": 0.25}
    st = EngineStats.from_dict(old)
    assert st.rounds == 4 and st.last_drift == 0.25
    assert st.pool_appends == 0
    # keys this build does not know (retired or from a future build) are
    # dropped, not fatal
    fut = dict(old, stage_wall_s={"fit": 1.5, "round_total": 2.0},
               some_future_counter=7, another_unknown="x")
    st2 = EngineStats.from_dict(fut)
    assert st2 == st
    assert "stage_wall_s" not in st2.as_dict()
    assert "some_future_counter" not in st2.as_dict()
    # round trip through as_dict is stable
    assert EngineStats.from_dict(st2.as_dict()) == st2
