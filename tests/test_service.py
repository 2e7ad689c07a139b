"""Exploration service: q-batch fantasy selection, checkpoint/resume, the
async flow pool and the on-disk evaluation cache.

The contract under test (ISSUE 4 + ISSUE 5 acceptance):
- a ``q=1`` service round selects bit-identical candidates to the existing
  incremental engine / sequential tuner, and ``BatchedBOEngine.select_q``
  with ``q=1`` and nothing pending is bitwise-identical to batched
  ``select``;
- fantasy appends are the *same math* as a real trailing-block update under
  frozen hyperparameters, and a refill's fantasy chain samples the frontier
  y* exactly ONCE (frozen across the chain);
- out-of-order worker completions do not change the trajectory — for the
  single-scenario service AND for the multi-scenario ``fleet_service``
  (per-scenario ticket-ordered exact-``min_done`` drains);
- a killed run resumed from its latest checkpoint reproduces the
  uninterrupted trajectory bit-exactly (in-process partial-run resume here;
  true SIGKILL subprocess resumes in ``test_sigkill_resume_bit_exact`` and
  ``test_fleet_cli_sigkill_resume_bit_exact``);
- the content-addressed disk cache is shared across processes, and its
  ``gc`` evicts least-recently-USED entries to a byte/age budget.
"""
import concurrent.futures as cf
import json
import os
import signal
import subprocess
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import FleetScenario, fleet_tuner, soc_tuner
from repro.core.engine import (BatchedBOEngine, BOEngine, _chol_refactor,
                               _v_chunk_refactor, _kernel)
from repro.core.icd import icd_from_data
from repro.core.sampling import soc_init
from repro.service import (FlowDiskCache, FlowPool, fleet_service,
                           latest_snapshot, load_snapshot, save_snapshot,
                           service_tuner, snapshot_path)
from repro.service.flowcache import CachedFlow
from repro.soc import VLSIFlow

KW = dict(T=5, n=12, b=8, gp_steps=30)


@pytest.fixture(scope="module")
def icd_setup(space, small_pool):
    """Shared (pool_icd, pool metrics) for engine-level tests."""
    flow = VLSIFlow(space, "resnet50")
    y_pool = np.asarray(flow(small_pool))
    trial = np.arange(12)
    v = icd_from_data(space, small_pool[trial], y_pool[trial])
    _, _, pool_icd = soc_init(space, small_pool, v, v_th=0.07, b=8, mu=0.1)
    return jnp.asarray(pool_icd, jnp.float32), y_pool


def _engine(pool_icd, y_pool, n0: int = 12, **kw) -> BOEngine:
    eng = BOEngine(pool_icd, incremental=True, gp_steps=30, warm_steps=5,
                   **kw)
    eng.observe(list(range(n0)), y_pool[:n0])
    return eng


# ------------------------------------------------------------- q-batch core
def test_select_q1_bitwise_parity_with_select(icd_setup):
    """select_q(q=1) IS today's round: same pick from the same key, and the
    service driver built on it reproduces soc_tuner exactly (below)."""
    pool_icd, y_pool = icd_setup
    key = jax.random.PRNGKey(0)
    for r in range(3):
        e1 = _engine(pool_icd, y_pool)
        e2 = _engine(pool_icd, y_pool)
        k = jax.random.fold_in(key, r)
        assert e2.select_q(k, 1) == [e1.select(k)]


def test_q1_service_round_matches_sequential_tuner(space, small_pool):
    """The full q=1 service loop (inline executor) is bit-identical to
    soc_tuner on the incremental engine — same rows, same metrics."""
    ref = soc_tuner(space, small_pool, VLSIFlow(space, "resnet50"),
                    key=jax.random.PRNGKey(3), incremental=True, **KW)
    svc = service_tuner(space, small_pool, VLSIFlow(space, "resnet50"),
                        key=jax.random.PRNGKey(3), q=1, executor="inline",
                        **KW)
    np.testing.assert_array_equal(ref.evaluated_rows, svc.evaluated_rows)
    np.testing.assert_array_equal(ref.y, svc.y)


def test_fantasy_update_matches_real_update(icd_setup):
    """Fantasy-vs-real consistency: the rank-1 fantasy append produces the
    SAME Cholesky bucket and V cache a full refactorization of the
    fantasy-extended training set would, under the frozen ``params_ref`` —
    the factorization depends only on x, so fantasy == real update exactly
    (to block-update tolerance)."""
    pool_icd, y_pool = icd_setup
    eng = _engine(pool_icd, y_pool)
    picks = eng.select_q(jax.random.PRNGKey(1), 2)
    assert len(set(picks)) == 2

    # Rebuild the fantasy-extended padded batch by hand: pick 0 replaced the
    # first pad row (position n).
    rows_pad, _, mask = eng._last_batch
    rows2 = np.asarray(rows_pad).copy()
    mask2 = np.asarray(mask).copy()
    n = eng._n_at_last_select
    rows2[n] = picks[0]
    mask2[n] = 0.0
    pool_flat = eng._pool_c.reshape(eng._N_pad, eng.d)
    x2 = pool_flat[rows2] + 10.0 * jnp.asarray(mask2)[:, None]
    L_full = _chol_refactor(eng._state.params_ref, x2, jnp.asarray(mask2))
    assert float(jnp.max(jnp.abs(eng._state.L - L_full))) < 5e-4
    V_full = jnp.stack([
        _v_chunk_refactor(eng._state.params_ref, L_full, x2, pc)
        for pc in eng._pool_c])
    assert float(jnp.max(jnp.abs(eng._state.V - V_full))) < 5e-4


def test_fantasy_mean_imputation_is_posterior_mean(icd_setup):
    """The 'mean' liar imputes exactly the standardized posterior mean the
    scoring path computes: reconstruct it as beta·V column under the frozen
    factorization and compare against a direct GP-style computation."""
    pool_icd, y_pool = icd_setup
    eng = _engine(pool_icd, y_pool)
    k = jax.random.PRNGKey(2)
    pick = eng.select(k)
    from repro.core.engine import _train_beta
    from repro.core.gp import _standardize

    rows_pad, y_pad, mask = eng._last_batch
    yn, y_mean, y_std = _standardize(jnp.asarray(y_pad), jnp.asarray(mask))
    beta = _train_beta(eng._state.L, yn)
    ci, col = pick // eng._C, pick % eng._C
    v_col = eng._state.V[ci, :, :, col]                     # [m, P]
    mean_engine = jnp.sum(beta * v_col, axis=1)             # [m]
    # independent reference: mean = k(x*, X) (K+Σ)⁻¹ y  via the Cholesky
    pool_flat = eng._pool_c.reshape(eng._N_pad, eng.d)
    x = pool_flat[jnp.asarray(rows_pad)] + 10.0 * jnp.asarray(mask)[:, None]
    pr = eng._state.params_ref
    for i in range(3):
        ks = _kernel((pr.log_ls[i], pr.log_var[i]), x,
                     pool_flat[pick][None], differentiable=False)[:, 0]
        vi = jax.scipy.linalg.solve_triangular(eng._state.L[i], ks,
                                               lower=True)
        ref = vi @ beta[i]
        assert abs(float(mean_engine[i]) - float(ref)) < 1e-4


def test_select_q_masks_pending_and_picks_distinct(icd_setup):
    pool_icd, y_pool = icd_setup
    eng = _engine(pool_icd, y_pool)
    pend = [40, 50]
    picks = eng.select_q(jax.random.PRNGKey(4), 3, pending=pend,
                         fantasy="cl_min")
    assert len(set(picks)) == 3
    assert not (set(picks) & set(pend))
    assert not (set(picks) & set(range(12)))
    assert eng.stats.fantasy_steps == len(pend) + 3 - 1


def test_out_of_order_observe_keeps_factorization_exact(icd_setup):
    """Fantasy rows never corrupt the kept Cholesky prefix, even when real
    completions are observed in a different order than they were fantasized
    and the train size crosses bucket boundaries: the next round's block
    update starts at bucket_floor(previous select's n), which always covers
    every position a fantasy chain wrote. Pins the soundness argument in
    select_q's trailing comment."""
    pool_icd, y_pool = icd_setup
    eng = BOEngine(pool_icd, incremental=True, gp_steps=25, warm_steps=5,
                   drift_tol=50.0)  # huge tol: force the block-update path
    eng.observe(list(range(7)), y_pool[:7])  # n=7 straddles bucket=8
    key = jax.random.PRNGKey(0)
    worst = 0.0
    for _ in range(6):
        key, ka, kb = jax.random.split(key, 3)
        picks = eng.select_q(ka, 4)
        for p in reversed(picks):  # observe OUT of fantasy/ticket order
            eng.observe([p], y_pool[p][None])
        eng.select(kb)  # block path under the stale-looking L/V
        worst = max(worst, eng.refactor_residual())
    assert eng.stats.block_updates > 0
    assert worst < 5e-4, worst


def test_frozen_ystar_one_frontier_resample_per_refill(icd_setup):
    """A whole select_q refill — q picks plus pending appends — pays exactly
    ONE O(q³) joint frontier draw: y* is sampled by the round phase and
    frozen across the fantasy chain."""
    pool_icd, y_pool = icd_setup
    eng = _engine(pool_icd, y_pool)
    eng.select_q(jax.random.PRNGKey(0), 4)
    assert eng.stats.frontier_resamples == 1
    eng.observe([200], y_pool[200][None])
    eng.select_q(jax.random.PRNGKey(1), 3, pending=[40, 50])
    assert eng.stats.frontier_resamples == 2
    assert eng.stats.fantasy_steps == 3 + (2 + 3 - 1)


# ------------------------------------------------------- batched q-batch
def _batched_engine(pool_icd, y_pool, n0=12, S=2, **kw) -> BatchedBOEngine:
    eng = BatchedBOEngine(jnp.stack([pool_icd] * S), incremental=True,
                          gp_steps=30, warm_steps=5, **kw)
    # distinct per-scenario training sets (offset windows into the pool)
    eng.observe([list(range(si * 3, si * 3 + n0)) for si in range(S)],
                [y_pool[si * 3:si * 3 + n0] for si in range(S)])
    return eng


def test_batched_select_q1_bitwise_parity_with_select(icd_setup):
    """ISSUE 5 acceptance: BatchedBOEngine.select_q(q=1, no pending) IS the
    batched round — identical [S] picks from identical keys."""
    pool_icd, y_pool = icd_setup
    keys = jnp.stack([jax.random.PRNGKey(7), jax.random.PRNGKey(8)])
    for r in range(3):
        e1 = _batched_engine(pool_icd, y_pool)
        e2 = _batched_engine(pool_icd, y_pool)
        k = jax.vmap(jax.random.fold_in, (0, None))(keys, r)
        p_sel = e1.select(k)
        p_q = e2.select_q(k, 1)
        assert p_q.shape == (2, 1)
        np.testing.assert_array_equal(np.asarray(p_sel), p_q[:, 0])


def test_batched_select_q_ragged_pending_masks(icd_setup):
    """Per-scenario pending lists of DIFFERENT lengths: every scenario gets
    q distinct fresh picks that avoid both its pending set and its own
    observations; only active (non-padded) steps count as fantasy appends."""
    pool_icd, y_pool = icd_setup
    eng = _batched_engine(pool_icd, y_pool)
    pend = [[40, 50, 60], [70]]          # ragged on purpose
    keys = jnp.stack([jax.random.PRNGKey(4), jax.random.PRNGKey(5)])
    picks = eng.select_q(keys, 3, pending=pend, fantasy="cl_min")
    assert picks.shape == (2, 3)
    for si in range(2):
        row_picks = [int(p) for p in picks[si]]
        assert len(set(row_picks)) == 3
        assert not (set(row_picks) & set(pend[si]))
        assert not (set(row_picks) & set(eng._rows[si]))
    # active appends: (3 pending + 2) + (1 pending + 2)
    assert eng.stats.fantasy_steps == (3 + 2) + (1 + 2)
    assert eng.stats.frontier_resamples == 1


def test_batched_select_q_no_pending_scenario_matches_round_pick(icd_setup):
    """A scenario with NO pending inside a fleet that has some elsewhere
    goes through masked no-op steps — its first pick must equal what the
    round itself would have picked (the no-ops are bitwise inert)."""
    pool_icd, y_pool = icd_setup
    keys = jnp.stack([jax.random.PRNGKey(5), jax.random.PRNGKey(6)])
    e1 = _batched_engine(pool_icd, y_pool)
    ref = np.asarray(e1.select(keys))        # plain round picks, both rows
    e2 = _batched_engine(pool_icd, y_pool)
    picks = e2.select_q(keys, 1, pending=[[40, 50], []])
    # scenario 1 had nothing pending: its pick is the round's own argmax
    assert int(picks[1, 0]) == int(ref[1])
    # scenario 0 fantasized its pending rows first: never re-proposes them
    assert int(picks[0, 0]) not in {40, 50}


def test_batched_select_q_validation(icd_setup):
    pool_icd, y_pool = icd_setup
    eng = _batched_engine(pool_icd, y_pool)
    keys = jnp.stack([jax.random.PRNGKey(0), jax.random.PRNGKey(1)])
    with pytest.raises(ValueError, match="fantasy"):
        eng.select_q(keys, 2, fantasy="nope")
    with pytest.raises(ValueError, match="entries"):
        eng.select_q(keys, 2, pending=[[1]])
    exact = BatchedBOEngine(jnp.stack([pool_icd] * 2), incremental=False,
                            gp_steps=30)
    exact.observe([list(range(12))] * 2, [y_pool[:12]] * 2)
    with pytest.raises(ValueError, match="incremental"):
        exact.select_q(keys, 2)


# ----------------------------------------------------------- fleet service
FKW = dict(T=4, n=10, b=6, gp_steps=30)


def test_fleet_service_fleet_of_one_bitwise_parity(space, small_pool):
    """A q=1 fleet-service of ONE scenario (inline executor) reproduces the
    fleet_tuner trajectory bit-for-bit — every evaluation is a batch-1
    dispatch in both drivers, so rows AND metrics match exactly."""
    scs = [FleetScenario("resnet50", seed=0)]
    ref = fleet_tuner(space, small_pool, scs, incremental=True, **FKW)
    svc = fleet_service(space, small_pool, scs, q=1, min_done=1,
                        executor="inline", **FKW)
    np.testing.assert_array_equal(ref.results[0].evaluated_rows,
                                  svc.results[0].evaluated_rows)
    np.testing.assert_array_equal(ref.results[0].y, svc.results[0].y)


@pytest.mark.parametrize("scs", [
    [FleetScenario("resnet50", seed=0), FleetScenario("resnet50", seed=1)],
    [FleetScenario("resnet50", seed=0), FleetScenario("transformer",
                                                      seed=1)],
], ids=["single-workload", "mixed-workload"])
def test_fleet_service_q1_matches_fleet_tuner_picks(space, small_pool, scs):
    """Multi-scenario fleets pick identical candidates; metrics agree to
    float tolerance only — whenever two scenarios' distinct picks share one
    fused flush, fleet_tuner evaluates them as a batch-N dispatch while the
    service pool dispatches per candidate, and XLA's batch-N vs batch-1
    programs differ in the last ulp (the prologue, evaluated through the
    same shared cache in both drivers, stays bitwise)."""
    ref = fleet_tuner(space, small_pool, scs, incremental=True, **FKW)
    svc = fleet_service(space, small_pool, scs, q=1, min_done=1,
                        executor="inline", **FKW)
    for a, b in zip(ref.results, svc.results):
        np.testing.assert_array_equal(a.evaluated_rows, b.evaluated_rows)
        np.testing.assert_allclose(a.y, b.y, rtol=1e-5)


def test_fleet_service_async_out_of_order_deterministic(space, small_pool):
    """Workers completing in reverse order leave every scenario's
    trajectory unchanged: per-scenario exact-min_done drains collect each
    scenario's OLDEST tickets whatever order the shared pool finishes
    them in. (The reversing executor releases each batch of 2 in reverse;
    2 divides every refill's submission count — mixed workloads so the
    fleet memo never swallows a submission — so its buffer is always
    flushed by the time a drain blocks on it.)"""
    scs = [FleetScenario("resnet50", seed=0),
           FleetScenario("transformer", seed=1)]
    kw = dict(q=2, min_done=1, **FKW)
    ref = fleet_service(space, small_pool, scs, executor="inline", **kw)
    rev = fleet_service(space, small_pool, scs,
                        executor=_ReversedBatchExecutor(2), **kw)
    for a, b in zip(ref.results, rev.results):
        np.testing.assert_array_equal(a.evaluated_rows, b.evaluated_rows)
        np.testing.assert_array_equal(a.y, b.y)


def test_fleet_service_kill_resume_bit_exact(space, small_pool, tmp_path):
    """Mid-flight crash simulation: run the full budget with per-cycle
    checkpoints, delete the newest snapshots (as if SIGKILLed right after
    an early one — in-flight picks and all), resume with the SAME budget;
    the resumed fleet must reproduce the uninterrupted run bit-exactly."""
    from repro.service.checkpoint import _list_snapshots

    scs = [FleetScenario("resnet50", seed=0),
           FleetScenario("transformer", seed=1)]
    kw = dict(q=2, min_done=1, executor="thread", **FKW)
    ck = str(tmp_path / "ck")
    full = fleet_service(space, small_pool, scs, checkpoint_dir=ck, **kw)
    snaps = _list_snapshots(ck, "ckpt")
    assert len(snaps) > 1
    for _, p in snaps[1:]:
        os.unlink(p)  # the "kill": only an early mid-flight snapshot is left
    res = fleet_service(space, small_pool, scs, checkpoint_dir=ck,
                        resume=True, **kw)
    for a, b in zip(full.results, res.results):
        np.testing.assert_array_equal(a.evaluated_rows, b.evaluated_rows)
        np.testing.assert_array_equal(a.y, b.y)


def test_fleet_service_cross_scenario_dedup(space, small_pool):
    """Two identical scenarios explore identical trajectories, and the
    shared pool pays each design point ONCE: the duplicate submission hits
    the in-flight/memo dedup instead of occupying a worker."""
    scs = [FleetScenario("resnet50", seed=0), FleetScenario("resnet50",
                                                            seed=0)]
    svc = fleet_service(space, small_pool, scs, q=2, min_done=1,
                        executor="thread", **FKW)
    a, b = svc.results
    np.testing.assert_array_equal(a.evaluated_rows, b.evaluated_rows)
    np.testing.assert_array_equal(a.y, b.y)
    stats = a.engine_stats["service"]
    total_bo = 2 * FKW["T"]
    dedup = (stats["pool_inflight_hits"] + stats["pool_cache_hits"]
             + stats["fleet_cache"]["memo_hits"])
    assert stats["pool_dispatched"] <= total_bo // 2 + 1
    assert dedup > 0


def test_fleet_service_retires_saturated_scenarios(space):
    """A budget larger than the candidate pool must not abort (or hang)
    the fleet: scenarios whose unevaluated rows run out retire gracefully
    with however many evaluations the pool could supply, never exceeding
    the pool size and never repeating a row."""
    tiny_pool = np.asarray(space.sample(jax.random.PRNGKey(9), 24))
    scs = [FleetScenario("resnet50", seed=0),
           FleetScenario("resnet50", seed=1)]
    fr = fleet_service(space, tiny_pool, scs, T=12, q=2, min_done=1,
                       executor="inline", n=8, b=4, gp_steps=15)
    for res in fr.results:
        rows = [int(r) for r in res.evaluated_rows]
        assert len(rows) == len(set(rows)) <= 24


def test_fleet_cli_sigkill_resume_bit_exact(tmp_path):
    """ISSUE 5 acceptance: a CLI fleet-async run SIGKILLed mid-flight and
    resumed from its latest snapshot reproduces the uninterrupted fleet
    bit-exactly, per scenario."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src")]
        + env.get("PYTHONPATH", "").split(os.pathsep))
    base = [sys.executable, "-m", "repro.service.cli", "fleet",
            "--workloads", "resnet50,transformer", "--seeds", "0",
            "--n-pool", "96", "--T", "3", "--q", "2", "--min-done", "1",
            "--executor", "thread", "--workers", "4", "--gp-steps", "15",
            "--n", "10", "--b", "8", "--quiet"]
    ref_out = str(tmp_path / "ref.json")
    subprocess.run(base + ["--out", ref_out], check=True, env=env)
    ck = str(tmp_path / "ck")
    killed = subprocess.run(
        base + ["--checkpoint-dir", ck, "--kill-after", "3",
                "--out", str(tmp_path / "k.json")], env=env)
    assert killed.returncode == -signal.SIGKILL
    assert latest_snapshot(ck) is not None
    assert not os.path.exists(str(tmp_path / "k.json"))  # died mid-run
    res_out = str(tmp_path / "res.json")
    subprocess.run(base + ["--checkpoint-dir", ck, "--resume",
                           "--out", res_out], check=True, env=env)
    ref = json.load(open(ref_out))
    res = json.load(open(res_out))
    assert ref["scenarios"].keys() == res["scenarios"].keys()
    for k in ref["scenarios"]:
        assert ref["scenarios"][k]["evaluated_rows"] == \
            res["scenarios"][k]["evaluated_rows"]
        assert ref["scenarios"][k]["y"] == res["scenarios"][k]["y"]


def test_select_q_validation(icd_setup):
    pool_icd, y_pool = icd_setup
    eng = _engine(pool_icd, y_pool)
    with pytest.raises(ValueError, match="fantasy"):
        eng.select_q(jax.random.PRNGKey(0), 2, fantasy="nope")
    exact = BOEngine(pool_icd, incremental=False, gp_steps=30)
    exact.observe(list(range(12)), y_pool[:12])
    with pytest.raises(ValueError, match="incremental"):
        exact.select_q(jax.random.PRNGKey(0), 2)


# --------------------------------------------------- async / out of order
class _ReversedBatchExecutor:
    """Test executor: buffers submissions and runs each batch of
    ``batch_size`` tasks in REVERSE submission order — a deterministic
    worst-case completion order for the reorder buffer."""

    def __init__(self, batch_size: int):
        self.batch_size = batch_size
        self._buf: list = []
        self._lock = threading.Lock()

    def submit(self, fn, *args, **kwargs) -> cf.Future:
        fut: cf.Future = cf.Future()
        with self._lock:
            self._buf.append((fut, fn, args, kwargs))
            ready = (len(self._buf) == self.batch_size)
            batch, self._buf = (self._buf, []) if ready else (self._buf, self._buf)
        if ready:
            for f, g, a, k in reversed(batch):
                try:
                    f.set_result(g(*a, **k))
                except BaseException as e:  # pragma: no cover
                    f.set_exception(e)
        return fut

    def shutdown(self, wait: bool = True, **_) -> None:
        for f, g, a, k in reversed(self._buf):
            try:
                f.set_result(g(*a, **k))
            except BaseException as e:  # pragma: no cover
                f.set_exception(e)
        self._buf = []


def test_async_out_of_order_completion_is_deterministic(space, small_pool):
    """Workers completing in reverse order leave the trajectory unchanged
    under ordered draining — observation order is pinned to ticket order."""
    kw = dict(T=4, n=12, b=8, gp_steps=30, q=2, min_done=2)
    ref = service_tuner(space, small_pool, VLSIFlow(space, "resnet50"),
                        key=jax.random.PRNGKey(3), executor="inline", **kw)
    rev = service_tuner(space, small_pool, VLSIFlow(space, "resnet50"),
                        key=jax.random.PRNGKey(3),
                        executor=_ReversedBatchExecutor(2), **kw)
    np.testing.assert_array_equal(ref.evaluated_rows, rev.evaluated_rows)
    np.testing.assert_array_equal(ref.y, rev.y)


def test_async_min_done_1_batchsize_is_timing_independent(space, small_pool):
    """With min_done=1 (fully async) the drain batch size — and therefore
    the refill cadence and PRNG consumption — must not depend on whether
    workers happen to be done already: instant-completion (inline) and
    batch-reversed executors must produce the same trajectory."""
    kw = dict(T=4, n=12, b=8, gp_steps=30, q=2, min_done=1)
    ref = service_tuner(space, small_pool, VLSIFlow(space, "resnet50"),
                        key=jax.random.PRNGKey(3), executor="inline", **kw)
    rev = service_tuner(space, small_pool, VLSIFlow(space, "resnet50"),
                        key=jax.random.PRNGKey(3),
                        executor=_ReversedBatchExecutor(2), **kw)
    np.testing.assert_array_equal(ref.evaluated_rows, rev.evaluated_rows)
    np.testing.assert_array_equal(ref.y, rev.y)


def test_flow_pool_ordered_drain_reorders_tickets(tmp_path):
    """FlowPool unit: reverse-completing executor + ordered drain releases
    results in strict ticket order with correct values; the disk cache is
    populated and short-circuits resubmission."""
    cache = FlowDiskCache(str(tmp_path / "fc"))
    pool = FlowPool(lambda idx: np.asarray(idx, np.float64) * 2.0,
                    workload="wl", executor=_ReversedBatchExecutor(3),
                    cache=cache)
    rows = [7, 3, 9]
    for r in rows:
        pool.submit(r, np.asarray([r, r + 1]))
    out = pool.drain(min_done=3, ordered=True)
    assert [o[1] for o in out] == rows                      # ticket order
    for _, r, y in out:
        np.testing.assert_array_equal(y, [2 * r, 2 * r + 2])
    # resubmit: all three now complete instantly from the cache
    for r in rows:
        pool.submit(r, np.asarray([r, r + 1]))
    assert pool.cache_hits == 3
    assert len(pool.drain(min_done=3)) == 3


# ------------------------------------------------------ checkpoint / resume
def test_snapshot_round_trip(tmp_path):
    tree = {"a": np.arange(6, dtype=np.float32).reshape(2, 3),
            "nested": {"k": jnp.ones((3,)), "s": "txt", "i": 4,
                       "lst": [1.5, None, True]},
            "hist": [{"round": 0, "adrs": 0.5}]}
    p = save_snapshot(snapshot_path(str(tmp_path), 3), tree)
    assert latest_snapshot(str(tmp_path)) == p
    back = load_snapshot(p)
    np.testing.assert_array_equal(back["a"], tree["a"])
    np.testing.assert_array_equal(back["nested"]["k"], np.ones((3,)))
    assert back["nested"]["lst"] == [1.5, None, True]
    assert back["hist"] == tree["hist"]


def test_service_checkpoint_resume_bit_exact(space, small_pool, tmp_path):
    """Partial run (checkpoints every completion) + resume == uninterrupted,
    bit for bit — rows, metrics, and the engine's onward picks."""
    kw = dict(T=6, n=12, b=8, gp_steps=30, q=2, min_done=2,
              executor="inline")
    full = service_tuner(space, small_pool, VLSIFlow(space, "resnet50"),
                         key=jax.random.PRNGKey(3), **kw)
    ck = str(tmp_path / "ck")
    service_tuner(space, small_pool, VLSIFlow(space, "resnet50"),
                  key=jax.random.PRNGKey(3), checkpoint_dir=ck,
                  **{**kw, "T": 4})
    res = service_tuner(space, small_pool, VLSIFlow(space, "resnet50"),
                        key=jax.random.PRNGKey(3), checkpoint_dir=ck,
                        resume=True, **kw)
    np.testing.assert_array_equal(full.evaluated_rows, res.evaluated_rows)
    np.testing.assert_array_equal(full.y, res.y)


def test_soc_tuner_checkpoint_resume_bit_exact(space, small_pool, tmp_path):
    """soc_tuner --resume: incremental AND exact engines both continue a
    partial run bit-exactly without re-paying any flow evaluation."""
    for incremental in (True, False):
        ck = str(tmp_path / f"ck_{incremental}")
        full = soc_tuner(space, small_pool, VLSIFlow(space, "resnet50"),
                         key=jax.random.PRNGKey(5), incremental=incremental,
                         **KW)
        flow_part = VLSIFlow(space, "resnet50")
        soc_tuner(space, small_pool, flow_part, key=jax.random.PRNGKey(5),
                  incremental=incremental, checkpoint_dir=ck,
                  **{**KW, "T": 2})
        flow_res = VLSIFlow(space, "resnet50")
        res = soc_tuner(space, small_pool, flow_res,
                        key=jax.random.PRNGKey(5), incremental=incremental,
                        checkpoint_dir=ck, resume=True, **KW)
        np.testing.assert_array_equal(full.evaluated_rows,
                                      res.evaluated_rows)
        np.testing.assert_array_equal(full.y, res.y)
        # resume replays NO past evaluations: 1 flow call per new round only
        assert flow_res.calls == KW["T"] - 2


def test_fleet_checkpoint_resume_bit_exact(space, small_pool, tmp_path):
    ck = str(tmp_path / "ckf")
    scs = [FleetScenario("resnet50", seed=0),
           FleetScenario("transformer", seed=1)]
    kw = dict(T=4, n=10, b=6, gp_steps=30, incremental=True)
    full = fleet_tuner(space, small_pool, scs, **kw)
    fleet_tuner(space, small_pool, scs, checkpoint_dir=ck, **{**kw, "T": 2})
    res = fleet_tuner(space, small_pool, scs, checkpoint_dir=ck, resume=True,
                      disk_cache=str(tmp_path / "dc"), **kw)
    for a, b in zip(full.results, res.results):
        np.testing.assert_array_equal(a.evaluated_rows, b.evaluated_rows)
        np.testing.assert_array_equal(a.y, b.y)


def test_resume_rejects_mismatched_pool_and_config(space, small_pool,
                                                   tmp_path):
    ck = str(tmp_path / "ck")
    kw = dict(T=3, n=12, b=8, gp_steps=30, q=2, min_done=2,
              executor="inline")
    service_tuner(space, small_pool, VLSIFlow(space, "resnet50"),
                  key=jax.random.PRNGKey(3), checkpoint_dir=ck, **kw)
    other_pool = np.asarray(space.sample(jax.random.PRNGKey(77), 256))
    with pytest.raises(ValueError, match="pool"):
        service_tuner(space, other_pool, VLSIFlow(space, "resnet50"),
                      key=jax.random.PRNGKey(3), checkpoint_dir=ck,
                      resume=True, **kw)
    with pytest.raises(ValueError, match="q="):
        service_tuner(space, small_pool, VLSIFlow(space, "resnet50"),
                      key=jax.random.PRNGKey(3), checkpoint_dir=ck,
                      resume=True, **{**kw, "q": 3, "min_done": 1})


def test_sigkill_resume_bit_exact(tmp_path):
    """THE acceptance run: a CLI service run SIGKILLed mid-flight (right
    after a checkpoint), resumed from its latest snapshot, reproduces the
    uninterrupted trajectory bit-exactly."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src")]
        + env.get("PYTHONPATH", "").split(os.pathsep))
    base = [sys.executable, "-m", "repro.service.cli", "--workload",
            "resnet50", "--n-pool", "96", "--T", "4", "--q", "2",
            "--min-done", "2", "--executor", "thread", "--workers", "2",
            "--gp-steps", "15", "--n", "10", "--b", "8", "--seed", "3",
            "--quiet"]
    ref_out = str(tmp_path / "ref.json")
    subprocess.run(base + ["--out", ref_out], check=True, env=env)
    ck = str(tmp_path / "ck")
    killed = subprocess.run(
        base + ["--checkpoint-dir", ck, "--kill-after", "2",
                "--out", str(tmp_path / "k.json")], env=env)
    assert killed.returncode == -signal.SIGKILL
    assert latest_snapshot(ck) is not None
    assert not os.path.exists(str(tmp_path / "k.json"))  # it died mid-run
    res_out = str(tmp_path / "res.json")
    subprocess.run(base + ["--checkpoint-dir", ck, "--resume",
                           "--out", res_out], check=True, env=env)
    ref = json.load(open(ref_out))
    res = json.load(open(res_out))
    assert ref["evaluated_rows"] == res["evaluated_rows"]
    assert ref["y"] == res["y"]


def test_flow_pool_collect_and_inflight_dedup(tmp_path):
    """FlowPool unit: per-submit workload routing, in-flight dedup of
    identical (workload, design point) submissions, and collect() releasing
    exactly the requested tickets in the requested order."""
    calls = []

    def flow(idx):
        calls.append(np.asarray(idx).copy())
        return np.asarray(idx, np.float64) * 2.0

    pool = FlowPool(flow, workload="wl", executor="thread", max_workers=2)
    t0 = pool.submit(3, np.asarray([3, 4]))
    t1 = pool.submit(3, np.asarray([3, 4]))            # identical: dedup
    t2 = pool.submit(5, np.asarray([5, 6]), workload="other")
    out = pool.collect([t1, t0])                        # caller's order
    assert [o[0] for o in out] == [t1, t0]
    for _, r, y in out:
        np.testing.assert_array_equal(y, [6, 8])
    (t, r, y), = pool.collect([t2])
    np.testing.assert_array_equal(y, [10, 12])
    pool.close()
    assert pool.dispatched == 2 and pool.inflight_hits == 1
    assert len(calls) == 2
    with pytest.raises(KeyError):
        pool.collect([t0])  # already drained


def test_flow_pool_submit_resolved_keeps_ticket_order(tmp_path):
    pool = FlowPool(lambda idx: np.asarray(idx, np.float64),
                    workload="wl", executor="inline")
    t0 = pool.submit(1, np.asarray([1]))
    t1 = pool.submit_resolved(9, np.asarray([99.0]))
    out = pool.drain(min_done=2, ordered=True)
    assert [o[0] for o in out] == [t0, t1]
    np.testing.assert_array_equal(out[1][2], [99.0])
    pool.close()



class _WorkerPlatformProbe:
    """Flow stand-in that reports the evaluating process's JAX platform as
    three flags: config pinned to cpu, backend is cpu, env says cpu."""

    def __call__(self, idx):
        import jax

        return np.asarray([[jax.config.jax_platforms == "cpu",
                            jax.default_backend() == "cpu",
                            os.environ.get("JAX_PLATFORMS") == "cpu"]],
                          np.float64)


def test_flow_pool_process_workers_pinned_to_cpu(space, small_pool,
                                                 monkeypatch):
    """A process worker evaluates on the host CPU backend whatever the
    caller's environment says (an accelerator belongs to the parent alone),
    and its flow metrics equal the inline executor's bit for bit."""
    flow = VLSIFlow(space, "resnet50")
    rows = [0, 7, 31]
    inline = FlowPool(flow, workload="resnet50", executor="inline")
    for r in rows:
        inline.submit(r, small_pool[r])
    want = [y for _, _, y in inline.drain(min_done=len(rows))]
    inline.close()
    # the spawned worker inherits no platform choice from the caller
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    pool = FlowPool(flow, workload="resnet50", executor="process",
                    max_workers=1)
    try:
        t = pool.submit(-1, np.zeros(1), workload="probe",
                        flow=_WorkerPlatformProbe())
        (_, _, flags), = pool.collect([t])
        np.testing.assert_array_equal(flags, [1.0, 1.0, 1.0])
        for r in rows:
            pool.submit(r, small_pool[r])
        got = [y for _, _, y in pool.drain(min_done=len(rows))]
    finally:
        pool.close()
    for g, w in zip(got, want):
        assert g.dtype == w.dtype and g.tobytes() == w.tobytes()


# -------------------------------------------------------------- cache gc
def _fill_cache(root, n, size=32):
    cache = FlowDiskCache(root)
    for i in range(n):
        cache.put("wl", np.asarray([i]), np.arange(size, dtype=np.float64))
        # stage mtimes 1 minute apart, oldest first
        path = cache._path(cache.key("wl", np.asarray([i])))
        t = 1_000_000 + i * 60
        os.utime(path, (t, t))
    return cache


def test_flow_cache_gc_max_bytes_evicts_lru(tmp_path):
    root = str(tmp_path / "fc")
    cache = _fill_cache(root, 4)
    entry_bytes = cache.entries()[0][1]
    stats = cache.gc(max_bytes=2 * entry_bytes)
    assert stats["removed"] == 2 and stats["kept"] == 2
    assert stats["kept_bytes"] <= 2 * entry_bytes
    # the two OLDEST entries went; the newest survive and still load
    assert cache.get("wl", np.asarray([0])) is None
    assert cache.get("wl", np.asarray([1])) is None
    np.testing.assert_array_equal(cache.get("wl", np.asarray([3])),
                                  np.arange(32, dtype=np.float64))


def test_flow_cache_gc_max_age_and_touch_on_read(tmp_path):
    root = str(tmp_path / "fc")
    cache = _fill_cache(root, 3)
    # reading entry 0 refreshes its mtime -> it is now the most recent
    assert cache.get("wl", np.asarray([0])) is not None
    now = 1_000_000 + 3 * 60
    stats = cache.gc(max_age_days=1.0, now=now + 86400 + 61)
    # entries 1 and 2 (mtimes now+ ~1-2 min) are older than a day relative
    # to `now + 1 day + 61s`; entry 0 was touched at wall-clock time (way
    # in the future of the staged mtimes) and survives
    assert stats["removed"] == 2
    assert cache.get("wl", np.asarray([0])) is not None
    assert cache.get("wl", np.asarray([1])) is None


def test_flow_cache_gc_validation(tmp_path):
    cache = FlowDiskCache(str(tmp_path / "fc"))
    with pytest.raises(ValueError, match="max_bytes"):
        cache.gc()
    with pytest.raises(ValueError, match="max_bytes"):
        cache.gc(max_bytes=-1)


def test_flow_cache_get_survives_failed_mtime_touch(tmp_path, monkeypatch):
    """Regression: a hit whose LRU mtime refresh fails (read-only root,
    racing gc) must still return the entry — recency is advisory."""
    cache = FlowDiskCache(str(tmp_path / "fc"))
    cache.put("wl", np.asarray([1]), np.asarray([2.5]))

    def _utime_raises(path, times=None):
        raise OSError("read-only file system")

    monkeypatch.setattr(os, "utime", _utime_raises)
    np.testing.assert_array_equal(cache.get("wl", np.asarray([1])), [2.5])
    assert cache.hits == 1 and cache.misses == 0


def test_flow_cache_gc_equal_mtime_tiebreak_is_deterministic(tmp_path):
    """Regression: entries sharing one mtime (coarse filesystem clocks)
    sort — and evict — in lexicographic path order, so concurrent workers
    running the same gc policy agree on what goes."""
    cache = FlowDiskCache(str(tmp_path / "fc"))
    for i in range(4):
        cache.put("wl", np.asarray([i]), np.arange(8, dtype=np.float64))
        os.utime(cache._path(cache.key("wl", np.asarray([i]))), (5, 5))
    entries = cache.entries()
    paths = [p for p, _, _ in entries]
    assert paths == sorted(paths)  # (mtime, path) tie-break
    stats = cache.gc(max_bytes=2 * entries[0][1])
    assert stats["removed"] == 2
    left = {p for p, _, _ in cache.entries()}
    assert left == set(paths[2:])  # lexicographically smallest went first


# ------------------------------------------------------------- disk cache
def test_disk_cache_hit_across_processes(tmp_path):
    """An entry written by another PROCESS is served from disk here — the
    cache is content-addressed and atomically written, so fleets/services
    sharing one root never duplicate flow work."""
    root = str(tmp_path / "fc")
    idx = np.asarray([3, 1, 4, 1, 5], np.int64)
    script = (
        "import numpy as np, sys\n"
        "from repro.service import FlowDiskCache\n"
        f"c = FlowDiskCache({root!r})\n"
        f"c.put('wl', np.asarray({idx.tolist()}), "
        "np.asarray([1.5, 2.5, 3.5]))\n")
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src")]
        + env.get("PYTHONPATH", "").split(os.pathsep))
    subprocess.run([sys.executable, "-c", script], check=True, env=env)
    cache = FlowDiskCache(root)
    got = cache.get("wl", idx)
    np.testing.assert_array_equal(got, [1.5, 2.5, 3.5])
    assert cache.get("other-wl", idx) is None  # workload is part of the key
    assert cache.hits == 1 and cache.misses == 1


def test_cached_flow_dedups_and_matches(space, small_pool):
    import tempfile

    with tempfile.TemporaryDirectory() as td:
        inner = VLSIFlow(space, "resnet50")
        cf_flow = CachedFlow(inner, td, "resnet50")
        idx = small_pool[:8]
        y1 = cf_flow(idx)
        y2 = cf_flow(idx)  # fully cached: no inner call
        np.testing.assert_array_equal(y1, y2)
        np.testing.assert_array_equal(y1, VLSIFlow(space, "resnet50")(idx))
        assert inner.calls == 1 and cf_flow.flow_calls == 1
        # partial overlap: one inner call for just the misses
        y3 = cf_flow(small_pool[4:12])
        assert inner.calls == 2 and inner.evaluated == 8 + 4
        np.testing.assert_array_equal(y3[:4], y1[4:])


def test_delayed_flow_sleeps_per_call(space, small_pool):
    from repro.soc import DelayedFlow

    flow = DelayedFlow(VLSIFlow(space, "resnet50"), 0.05)
    t0 = time.time()
    y = flow(small_pool[:4])
    assert time.time() - t0 >= 0.05
    np.testing.assert_array_equal(
        y, VLSIFlow(space, "resnet50")(small_pool[:4]))
