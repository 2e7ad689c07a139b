"""Device-resident incremental BO engine — the Algorithm 3 hot path.

``soc_tuner`` / ``fleet_tuner`` historically rebuilt the surrogate from
nothing every round: a cold-started Adam fit from ``_default_params``, a full
O(n³) train Cholesky, K(train, pool) recomputed against a pool that is static
for all T rounds, and the [N]-sized score vector round-tripped through host
NumPy for masking and argmax. :class:`BOEngine` keeps the surrogate alive
across rounds instead:

* **warm starts** — each round's Adam fit resumes from the previous round's
  ``GPParams`` and runs a short ``warm_steps`` schedule instead of a cold
  ``gp_steps`` restart (``warm_start=False`` restores cold fits);
* **incremental posterior** — appending k ≤ ``bucket`` rows extends the train
  Cholesky by a rank-k *block* update (recompute only the trailing rows of L)
  instead of refactorizing; a full factorization happens only on bucket
  growth or when the warm-fitted hyperparameters drift past ``drift_tol``
  from the ones the factorization was built with;
* **chunked pool streaming** — the pool axis lives in column chunks: the
  cached ``V = L⁻¹·K(train_pad, pool)`` is stored ``[nc, m, P, C]`` and every
  O(N) stage (trailing-row V updates, posterior moments, masking, argmax)
  runs as a ``lax.scan`` over chunks with an online running-argmax carry, so
  no [P, N] kernel product, [N] score vector, or [S_frontier, N, m] MES
  broadcast is ever materialized whole. ``pool_chunk=None`` is one chunk
  covering the pool (the monolithic regime); any other chunking is
  *numerically pinned* to it — posterior moments use fixed-order sequential
  accumulation (``lax.fori_loop``) instead of width-dependent GEMV
  reductions, so every chunk size produces bit-identical scores and selects
  the identical candidate (``tests/test_pool_scaling.py``). This is what
  lets ``n_pool`` grow from the paper's 2 500 toward 10⁵–10⁶ (see
  ``docs/scaling.md`` for the memory model);
* **device-side selection** — the never-re-evaluate mask is scattered as
  ``-inf`` and the argmax taken inside the jitted program, so a round is a
  single XLA dispatch whose only host transfer is the chosen row index.

The **update/refactor policy** in one place: let ``params_ref`` be the
hyperparameters of the current factorization. Every round the warm fit
advances ``params``; if ``max |params − params_ref|`` (over all log-domain
leaves) exceeds ``drift_tol``, or the padded training size grew a bucket, the
engine refactorizes under the fresh ``params`` and re-syncs ``params_ref``;
otherwise it keeps ``params_ref`` frozen and block-updates L and V. The
posterior is therefore always *exact* for ``params_ref`` (the block update is
algebraically identical to a full factorization — see
``tests/test_engine.py``); staleness is bounded by ``drift_tol`` and by the
bucket period, never accumulated silently.

``BOEngine(incremental=False)`` is the exact-equivalence escape hatch: it
executes the historical per-round computation (``fit_gp`` + ``imoo_scores`` +
host-side masking/argmax) call-for-call, reproducing the seed ``soc_tuner``
trajectory bit-for-bit. :class:`BatchedBOEngine` is the same engine with a
leading scenario axis — the fleet runner's backend — whose exact path
likewise reproduces today's ``fit_gp_batch``/``imoo_scores_batch`` rounds.
``BatchedBOEngine(..., mesh=...)`` additionally shards the scenario axis over
a device mesh with ``shard_map`` (scenarios are embarrassingly parallel —
one scenario group per device, no collectives inside a round); the per-round
host sync collapses to the fleet-wide drift maximum plus one gather of the
[S] picks.

Two service-facing extensions (``repro.service`` builds on both):

* **q-batch fantasy selection** — :meth:`BOEngine.select_q` picks ``q``
  candidates per round: after each pick it *imputes* the outcome (posterior
  mean, or a constant liar) in standardized target space, pushes the fantasy
  row through the same rank-1 trailing Cholesky + V-cache block update the
  real rounds use, re-scores the pool and picks again — all device-resident
  and pool-chunk-compatible. ``q=1`` (with no pending evaluations) delegates
  to :meth:`BOEngine.select` verbatim, so it is bit-identical to today's
  round by construction. In-flight evaluations of an *async* driver are
  handed in as ``pending`` and fantasized before any new pick, which is what
  lets a round start before all previous picks have returned. Fantasy rows
  only ever live in the trailing ``[bucket-floor(n), P)`` region that the
  next real round recomputes anyway, so fantasy state never leaks into real
  posterior math.
* **checkpoint/resume** — :meth:`BOEngine.state_dict` /
  :meth:`BOEngine.load_state_dict` (and the batched twins) serialize the
  complete engine state — train rows/targets, warm ``GPParams``, the
  ``params_ref`` factorization snapshot, the Cholesky bucket ``L`` and
  chunked ``V`` cache, pad bookkeeping, stats — as numpy arrays + scalars.
  A restored engine continues the trajectory *bit-exactly* (same picks, same
  refactor decisions); ``repro.service.checkpoint`` owns the on-disk format.

Engine-state buffers are **donated** through the round dispatches
(``jax.jit(..., donate_argnames=...)``): the update scan writes the new V
cache into the old V's storage instead of holding both copies live, which is
what keeps the transient footprint flat in the 10⁵–10⁶-candidate regime
(measured in ``BENCH_pool.json``).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec

from repro.kernels.backend import (auto_chunk, resolve_round_backend,
                                   round_score_auto)
from repro.obs.spans import span

from .acquisition import imoo_scores, imoo_scores_batch, mes_information_gain
from .gp import (JITTER, PAD_BUCKET, GPParams, _default_params, _fit, _kernel,
                 _mm, _standardize, fit_gp, fit_gp_batch, pad_training)

__all__ = ["BOEngine", "BatchedBOEngine", "EngineStats", "FANTASY_MODES"]

#: supported imputation rules for fantasy (q-batch / pending) selection:
#: ``"mean"`` — posterior mean at the pick (kriging believer); ``"cl_min"`` /
#: ``"cl_max"`` — constant liar at the worst / best observed target per
#: objective (in the engine's negated, standardized target space, so
#: ``cl_min`` is the pessimistic liar of the maximization problem).
FANTASY_MODES = ("mean", "cl_min", "cl_max")

#: version tag of the ``state_dict`` layout (bumped on incompatible change).
ENGINE_STATE_FORMAT = 1


@dataclasses.dataclass
class EngineStats:
    """Host-side counters for one engine run (read by ``engine_bench``)."""

    rounds: int = 0
    refactors: int = 0       # full O(P³) factorizations
    block_updates: int = 0   # rank-k trailing-block updates
    dispatches: int = 0      # top-level jitted program launches
    fantasy_steps: int = 0   # rank-1 fantasy appends (q-batch / pending)
    frontier_resamples: int = 0  # O(q³) joint frontier draws (1/refill)
    last_drift: float = 0.0  # max |params − params_ref| at the last round
    # per-scenario factorization decisions (batched engine): in a mixed
    # round only the drifting scenarios refactor, the rest block-update
    scenario_refactors: int = 0
    scenario_block_updates: int = 0
    mixed_rounds: int = 0    # rounds where the fleet split ref/update
    # mutable-pool bookkeeping: columns appended/replaced and the V chunks
    # recomputed for them (never a full refactor)
    pool_appends: int = 0
    pool_replacements: int = 0
    v_chunk_refreshes: int = 0

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: dict) -> "EngineStats":
        """Build from a (possibly old or newer) snapshot dict: unknown keys
        are dropped, missing keys keep their defaults — so checkpoints
        written before a stats field existed (and ones written after a field
        this build doesn't know about) both load."""
        fields = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in d.items() if k in fields})

    def fold_into(self, registry, *, prefix: str = "engine") -> None:
        """Accumulate this run's counters into an
        :class:`repro.obs.MetricsRegistry` (duck-typed — anything with
        ``counter(name, help).inc(v, **labels)``). Call ONCE per finished
        engine (the stats are cumulative over its lifetime)."""
        for k in ("rounds", "refactors", "block_updates", "dispatches",
                  "fantasy_steps", "frontier_resamples",
                  "scenario_refactors", "scenario_block_updates",
                  "mixed_rounds", "pool_appends", "pool_replacements",
                  "v_chunk_refreshes"):
            v = float(getattr(self, k))
            if v:
                registry.counter(f"{prefix}_{k}_total",
                                 f"engine {k.replace('_', ' ')}").inc(v)


class EngineState(NamedTuple):
    """Device-resident carry between rounds (a pytree).

    The pool axis is chunked: ``V`` holds ``nc`` column chunks of ``C``
    candidates each (``nc·C = N_pad ≥ N``; one chunk of C = N when
    ``pool_chunk=None``). The batched engine carries a leading [S] axis on
    every leaf.
    """

    params: GPParams      # warm-evolving fit hyperparameters
    params_ref: GPParams  # hyperparameters of the current factorization
    L: jnp.ndarray        # [m, P, P] Cholesky of K(params_ref) + noise
    V: jnp.ndarray        # [nc, m, P, C] L⁻¹ · K(train_pad, pool chunk)


def _params_to_np(p: GPParams) -> dict:
    return {"log_ls": np.asarray(p.log_ls), "log_var": np.asarray(p.log_var),
            "log_noise": np.asarray(p.log_noise)}


def _params_from_np(d: dict) -> GPParams:
    return GPParams(jnp.asarray(d["log_ls"], jnp.float32),
                    jnp.asarray(d["log_var"], jnp.float32),
                    jnp.asarray(d["log_noise"], jnp.float32))


def _drift(params: GPParams, params_ref: GPParams) -> jnp.ndarray:
    """max |Δ| over all log-domain hyperparameter leaves."""
    return jnp.maximum(
        jnp.max(jnp.abs(params.log_ls - params_ref.log_ls)),
        jnp.maximum(jnp.max(jnp.abs(params.log_var - params_ref.log_var)),
                    jnp.max(jnp.abs(params.log_noise - params_ref.log_noise))))


# ------------------------------------------------------------ factorization
def _chol_one(log_ls, log_var, log_noise, x, mask):
    """Full train-Cholesky for one objective (no pool work)."""
    P = x.shape[0]
    K = _kernel((log_ls, log_var), x, x, differentiable=False)
    K = K + (jnp.exp(log_noise) + JITTER) * jnp.eye(P) + jnp.diag(1e6 * mask)
    return jnp.linalg.cholesky(K)


def _chol_refactor(params: GPParams, x, mask):
    return jax.vmap(_chol_one, in_axes=(0, 0, 0, None, None))(
        params.log_ls, params.log_var, params.log_noise, x, mask)


def _chol_block(params_ref: GPParams, L, x, mask, s0: int):
    """Rank-k extension of L: recompute rows [s0, P) only.

    Valid whenever rows [0, s0) of ``x`` are unchanged since the last
    factorization (real rows form a prefix and only appended rows + trailing
    pad rows differ round-to-round). For the block partition
    ``K = [[K11, K12], [K21, K22]]`` the Cholesky factor satisfies
    ``L21 = (L11⁻¹ K12)ᵀ`` and ``L22 = chol(K22 − L21 L21ᵀ)`` — exactly what a
    full refactorization would produce, at O(P²·k) instead of O(P³).
    """

    def one(log_ls, log_var, log_noise, Li):
        xa, xb = x[:s0], x[s0:]
        B = x.shape[0] - s0
        K12 = _kernel((log_ls, log_var), xa, xb, differentiable=False)
        K22 = _kernel((log_ls, log_var), xb, xb, differentiable=False)
        K22 = (K22 + (jnp.exp(log_noise) + JITTER) * jnp.eye(B)
               + jnp.diag(1e6 * mask[s0:]))
        L11 = Li[:s0, :s0]
        L21 = jax.scipy.linalg.solve_triangular(L11, K12, lower=True).T
        L22 = jnp.linalg.cholesky(K22 - _mm(L21, L21.T))
        return Li.at[s0:, :s0].set(L21).at[s0:, s0:].set(L22)

    return jax.vmap(one)(params_ref.log_ls, params_ref.log_var,
                         params_ref.log_noise, L)


def _v_chunk_refactor(params_ref: GPParams, L, x, pc):
    """Fresh V for one pool chunk ``pc`` [C, d]: L⁻¹·K(x, pc) per objective."""

    def one(log_ls, log_var, Li):
        Ks = _kernel((log_ls, log_var), x, pc, differentiable=False)  # [P, C]
        return jax.scipy.linalg.solve_triangular(Li, Ks, lower=True)

    return jax.vmap(one)(params_ref.log_ls, params_ref.log_var, L)


def _v_chunk_block(params_ref: GPParams, L, Vc, x, pc, s0: int):
    """Rank-k extension of one V chunk: recompute rows [s0, P) only."""

    def one(log_ls, log_var, Li, Vi):
        Ksb = _kernel((log_ls, log_var), x[s0:], pc,
                      differentiable=False)                       # [B, C]
        L21, L22 = Li[s0:, :s0], Li[s0:, s0:]
        Vb = jax.scipy.linalg.solve_triangular(
            L22, Ksb - _mm(L21, Vi[:s0]), lower=True)
        return Vi.at[s0:].set(Vb)

    return jax.vmap(one)(params_ref.log_ls, params_ref.log_var, L, Vc)


# ----------------------------------------------------------------- scoring
def _col_moments(log_var, beta_i, Vi):
    """Posterior mean/std for every column of one objective's V chunk.

    Sequential fixed-order accumulation (``fori_loop`` over the P train
    rows), NOT a GEMV: XLA's matmul reductions change last-ulp results with
    the output width, while this form makes the moments — and therefore the
    scores and the argmax — independent of the chunk size. The chunked-vs-
    monolithic bit-parity of the whole engine rests on this function
    (pinned by ``tests/test_pool_scaling.py``).
    """

    def body(p, acc):
        mu, ss = acc
        return mu + beta_i[p] * Vi[p], ss + Vi[p] * Vi[p]

    mu, ss = jax.lax.fori_loop(
        1, Vi.shape[0], body, (beta_i[0] * Vi[0], Vi[0] * Vi[0]))
    var = jnp.exp(log_var) - ss
    return mu, jnp.sqrt(jnp.maximum(var, 1e-10))


def _train_beta(L, yn):
    """[m, P] whitened targets β = L⁻¹·y per objective."""
    return jax.vmap(
        lambda Li, yi: jax.scipy.linalg.solve_triangular(Li, yi, lower=True)
    )(L, yn.T)


def _frontier_ystar(params_ref: GPParams, L, beta, x, xq, y_mean, y_std, key,
                    s: int):
    """[s, m] sampled Pareto-frontier maxima over the ``xq`` [q, d] subset.

    Mirrors ``gp_joint_samples`` + ``frontier_maxima``: the O(q³) joint draw
    runs on the frontier subset only, so it is independent of the pool size
    and of the chunking.
    """
    m = beta.shape[0]
    q = xq.shape[0]

    def one(log_ls, log_var, Li, bi, k):
        Ks = _kernel((log_ls, log_var), x, xq, differentiable=False)  # [P, q]
        Vs = jax.scipy.linalg.solve_triangular(Li, Ks, lower=True)
        mean_q, _ = _col_moments(log_var, bi, Vs)
        Kqq = _kernel((log_ls, log_var), xq, xq, differentiable=False)
        cov = Kqq - _mm(Vs.T, Vs)
        jit_ = 1e-4 * jnp.exp(log_var) + 1e-6
        Lq = jnp.linalg.cholesky(cov + jit_ * jnp.eye(q))
        eps = jax.random.normal(k, (q, s))
        return mean_q[:, None] + _mm(Lq, eps)                         # [q, s]

    keys = jax.random.split(key, m)
    samp = jax.vmap(one)(params_ref.log_ls, params_ref.log_var, L, beta, keys)
    samp = jnp.transpose(samp, (2, 1, 0)) * y_std + y_mean  # [s, q, m]
    return jnp.max(samp, axis=1)                            # [s, m]


def _score_chunk(params_ref: GPParams, beta, Vc, y_mean, y_std, ystar,
                 evalm_c, weights):
    """Masked IMOO scores for one V chunk ``[m, P, C]`` -> ``[C]``."""
    mean, std = jax.vmap(_col_moments)(params_ref.log_var, beta, Vc)
    mean_d = mean.T * y_std + y_mean            # [C, m], de-standardized
    std_d = std.T * y_std
    scores = mes_information_gain(mean_d, std_d, ystar, weights)
    return jnp.where(evalm_c, -jnp.inf, scores)


def _select_chunks(params_ref: GPParams, beta, ystar, V, y_mean, y_std,
                   evalm_c, base, weights):
    """Whole-pool argmax from the chunked V cache (one scenario) under a
    precomputed whitened-target ``beta`` and frontier sample ``ystar``.

    Scans the chunks with an online running-max carry; cross-chunk ties keep
    the earlier chunk (strict ``>``) and in-chunk ``argmax`` keeps the first
    column, reproducing monolithic first-index-wins tie semantics exactly.
    ``ystar`` is sampled by the caller (:func:`_frontier_ystar`) — the round
    samples it ONCE and every fantasy step of the same refill re-scores
    under that *frozen* sample (standard MES q-batch practice), so a chain
    never re-pays the O(q³) joint frontier draw.
    """

    def step(carry, inp):
        best_val, best_idx = carry
        Vc, em, b0 = inp
        scores = _score_chunk(params_ref, beta, Vc, y_mean, y_std, ystar, em,
                              weights)
        v = jnp.max(scores)
        i = jnp.argmax(scores).astype(jnp.int32)
        take = v > best_val
        return (jnp.where(take, v, best_val),
                jnp.where(take, b0 + i, best_idx)), None

    init = (jnp.asarray(-jnp.inf, jnp.float32), jnp.asarray(0, jnp.int32))
    (_, nxt), _ = jax.lax.scan(step, init, (V, evalm_c, base))
    return nxt


def _beta_ystar(params_ref: GPParams, L, x, yn, y_mean, y_std, pool_c,
                sub_rows, key, *, s: int):
    """Whitened targets + ONE sampled frontier maximum for a round/refill."""
    nc, C, d = pool_c.shape
    xq = pool_c.reshape(nc * C, d)[sub_rows]
    beta = _train_beta(L, yn)
    ystar = _frontier_ystar(params_ref, L, beta, x, xq, y_mean, y_std, key, s)
    return beta, ystar


@functools.partial(jax.jit,
                   static_argnames=("steps", "s", "s0", "select", "fused"),
                   donate_argnames=("state",))
def _round_seq(state: EngineState, rows_pad, y_pad, mask, pool_c, evalm_c,
               base, sub_rows, key, force_refactor, drift_tol, weights, *,
               steps: int, s: int, s0: int, select: bool = True,
               fused: bool = False):
    """One full BO round as a single XLA dispatch: warm fit → drift check →
    block-update-or-refactor (``lax.cond``) → frontier sample →
    chunk-scanned score + argmax.

    ``state`` is donated: the update scan writes the new L/V into the old
    buffers' storage, so the engine never holds two V caches live.
    ``select=False`` skips the scoring scan and returns ``nxt = -1`` — the
    q-batch path uses it when in-flight evaluations must be fantasized
    before the round's first real pick is taken. The sampled frontier
    ``ystar`` is returned either way: it is the ONE sample the whole
    refill's fantasy chain re-scores under (frozen y*).

    ``fused=True`` (static — resolved per call by the engine from
    ``REPRO_ROUND_BACKEND``, see ``kernels.backend.resolve_round_backend``)
    replaces the staged V-update scan + scoring scan with ONE fused Pallas
    launch per pool chunk (``kernels/round_fused``) that keeps the V update,
    posterior moments, MES scores and the running argmax in VMEM. It selects
    the identical candidate (first-index-wins ties included — pinned by
    ``tests/test_kernels.py``); ``fused=False`` keeps the historical
    computation op for op, which is what the golden trajectory fixtures
    pin.

    The stages carry ``jax.named_scope`` names (``fit``, ``factor``,
    ``v_update``, ``frontier``, ``score``; the fused launch, which updates
    V and scores in one kernel, is ``score``), so the device trace can be
    split by stage; a scope changes op metadata only."""
    nc, C, d = pool_c.shape
    pool_flat = pool_c.reshape(nc * C, d)
    with jax.named_scope("fit"):
        x = pool_flat[rows_pad] + 10.0 * mask[:, None]  # pad_training's rule
        yn, y_mean, y_std = _standardize(y_pad, mask)
        params = _fit(state.params, x, yn, mask, steps=steps)
        drift = _drift(params, state.params_ref)
    with jax.named_scope("factor"):
        if s0 <= 0:  # statically known: nothing reusable — always refactor
            do_ref = jnp.asarray(True)
        else:
            do_ref = jnp.logical_or(force_refactor, drift > drift_tol)
        # On refactor the factorization adopts the fresh fit; resolve
        # params_ref first so both L/V branches factor under one snapshot.
        params_ref = jax.tree.map(lambda a, b: jnp.where(do_ref, a, b),
                                  params, state.params_ref)
        if s0 <= 0:
            L = _chol_refactor(params_ref, x, mask)
        else:
            L = jax.lax.cond(
                do_ref,
                lambda: _chol_refactor(params_ref, x, mask),
                lambda: _chol_block(params_ref, state.L, x, mask, s0))

    if fused and select:
        # Fused round: beta/y* first (independent of V), then one Pallas
        # launch per chunk does V-update + moments + MES + argmax in VMEM.
        with jax.named_scope("frontier"):
            beta, ystar = _beta_ystar(params_ref, L, x, yn, y_mean, y_std,
                                      pool_c, sub_rows, key, s=s)

        def _fused(s0f):
            return round_score_auto(params_ref, L, state.V, x, beta, ystar,
                                    pool_c, evalm_c, base, y_mean, y_std,
                                    weights, s0=s0f, backend="pallas")

        with jax.named_scope("score"):
            if s0 <= 0:
                V, nxt = _fused(0)
            else:
                V, nxt = jax.lax.cond(do_ref, lambda: _fused(0),
                                      lambda: _fused(s0))
        return EngineState(params, params_ref, L, V), nxt, do_ref, drift, \
            ystar

    def vstep(_, inp):
        Vc_old, pc = inp
        if s0 <= 0:
            return None, _v_chunk_refactor(params_ref, L, x, pc)
        return None, jax.lax.cond(
            do_ref,
            lambda: _v_chunk_refactor(params_ref, L, x, pc),
            lambda: _v_chunk_block(params_ref, L, Vc_old, x, pc, s0))

    with jax.named_scope("v_update"):
        _, V = jax.lax.scan(vstep, None, (state.V, pool_c))
    with jax.named_scope("frontier"):
        beta, ystar = _beta_ystar(params_ref, L, x, yn, y_mean, y_std,
                                  pool_c, sub_rows, key, s=s)
    if select:
        with jax.named_scope("score"):
            nxt = _select_chunks(params_ref, beta, ystar, V, y_mean, y_std,
                                 evalm_c, base, weights)
    else:
        nxt = jnp.asarray(-1, jnp.int32)
    return EngineState(params, params_ref, L, V), nxt, do_ref, drift, ystar


# ------------------------------------------------------- fantasy (q-batch)
def _liar_target(liar: str, mean_std, yn, mask):
    """Imputed standardized target [m] for one fantasy row (see
    ``FANTASY_MODES``; targets live in the engine's negated/standardized
    space, so ``cl_min`` is the pessimistic liar of the maximization)."""
    if liar == "mean":
        return mean_std
    pad = mask[:, None] > 0
    if liar == "cl_min":
        return jnp.min(jnp.where(pad, jnp.inf, yn), axis=0)
    return jnp.max(jnp.where(pad, -jnp.inf, yn), axis=0)  # cl_max


def _fantasy_append(params_ref: GPParams, L, V, rows_pad, yn, mask, pool_c,
                    pick, pos, *, s0: int, liar: str):
    """Append ONE fantasy observation to (L, V, rows, mask, yn).

    The picked pool row replaces the pad row at position ``pos``: its target
    is imputed under the *current* posterior (``_liar_target``), then L and
    every V chunk are extended by the same rank-k trailing-block update a
    real round uses (``s0`` = bucket-floored count of real rows, so every
    fantasy row of the batch lives in the recomputed ``[s0, P)`` region and
    one compiled program serves all q-1 steps — ``pos``/``pick`` are traced).
    Shared verbatim by the sequential and the vmapped batched fantasy steps.
    """
    nc, C, d = pool_c.shape
    pool_flat = pool_c.reshape(nc * C, d)
    ci = pick // C
    col = pick % C

    # Imputed target under the CURRENT state — the same fixed-order
    # accumulation the scoring path uses, so "impute the posterior mean"
    # means exactly the mean that ranked this candidate.
    beta = _train_beta(L, yn)                                    # [m, P]
    Vc = jax.lax.dynamic_index_in_dim(V, ci, axis=0, keepdims=False)
    Vcol = jax.lax.dynamic_index_in_dim(Vc, col, axis=2, keepdims=False)
    P = Vcol.shape[1]
    mean_std = jax.lax.fori_loop(
        1, P, lambda p, acc: acc + beta[:, p] * Vcol[:, p],
        beta[:, 0] * Vcol[:, 0])                                  # [m]
    target = _liar_target(liar, mean_std, yn, mask)

    rows2 = rows_pad.at[pos].set(pick)
    mask2 = mask.at[pos].set(0.0)
    yn2 = yn.at[pos].set(target)
    x2 = pool_flat[rows2] + 10.0 * mask2[:, None]
    if s0 <= 0:  # statically known: no reusable prefix (tiny first rounds)
        L2 = _chol_refactor(params_ref, x2, mask2)
        _, V2 = jax.lax.scan(
            lambda _, pc: (None, _v_chunk_refactor(params_ref, L2, x2, pc)),
            None, pool_c)
    else:
        L2 = _chol_block(params_ref, L, x2, mask2, s0)
        _, V2 = jax.lax.scan(
            lambda _, inp: (None, _v_chunk_block(params_ref, L2, inp[0], x2,
                                                 inp[1], s0)),
            None, (V, pool_c))
    return L2, V2, rows2, mask2, yn2


def _fused_rescore(params_ref: GPParams, L, V, rows_pad, mask, pool_c,
                   evalm_c, base, weights, y_mean, y_std, ystar, beta):
    """Score-only fused launch (``s0 = P``): re-rank the pool under an
    already-updated V cache — the fantasy chain's fused re-score."""
    nc, C, d = pool_c.shape
    x = pool_c.reshape(nc * C, d)[rows_pad] + 10.0 * mask[:, None]
    _, nxt = round_score_auto(params_ref, L, V, x, beta, ystar, pool_c,
                              evalm_c, base, y_mean, y_std, weights,
                              s0=V.shape[-2], backend="pallas")
    return nxt


@functools.partial(jax.jit,
                   static_argnames=("s0", "liar", "return_pick", "fused"),
                   donate_argnames=("L", "V"))
def _fantasy_step(params_ref: GPParams, L, V, rows_pad, yn, mask, pool_c,
                  evalm_c, base, weights, y_mean, y_std, ystar, pick, pos, *,
                  s0: int, liar: str, return_pick: bool,
                  fused: bool = False):
    """One sequential fantasy append (+ optional re-score under the frozen
    ``ystar`` sampled by the refill's round — no per-step frontier resample).
    ``return_pick=False`` skips the O(N) scoring scan (used while fantasizing
    pending in-flight evaluations that are not the last before a new pick).
    L and V are donated — the fantasy chain reuses one set of buffers.
    ``fused=True`` routes the re-score through the score-only fused Pallas
    launch (the append itself stays staged — a rank-1 trailing update has no
    inter-stage pool traffic to fuse away).
    """
    nc, C, _ = pool_c.shape
    L2, V2, rows2, mask2, yn2 = _fantasy_append(
        params_ref, L, V, rows_pad, yn, mask, pool_c, pick, pos, s0=s0,
        liar=liar)
    evalm2 = evalm_c.at[pick // C, pick % C].set(True)
    if return_pick:
        beta2 = _train_beta(L2, yn2)
        if fused:
            nxt = _fused_rescore(params_ref, L2, V2, rows2, mask2, pool_c,
                                 evalm2, base, weights, y_mean, y_std, ystar,
                                 beta2)
        else:
            nxt = _select_chunks(params_ref, beta2, ystar, V2, y_mean, y_std,
                                 evalm2, base, weights)
    else:
        nxt = jnp.asarray(-1, jnp.int32)
    return L2, V2, rows2, mask2, yn2, evalm2, nxt


def _fantasy_batch_impl(params_ref: GPParams, L, V, rows_pad, yn, mask,
                        pool_c, evalm_c, base, weights, y_mean, y_std, ystar,
                        pick, pos, active, *, s0: int, liar: str,
                        return_pick: bool, fused: bool = False):
    """Batched fantasy step: every scenario appends (or skips) one fantasy
    row in lockstep, then (optionally) re-scores under its frozen ``ystar``.

    ``active`` [S] masks per-scenario no-op steps — scenarios whose pending
    list is shorter than the fleet maximum are front-padded with inactive
    steps, so one vmapped program serves ragged pending sets. An inactive
    step leaves the scenario's state untouched (``jnp.where`` select) and,
    when ``return_pick`` is set, scores the *unmodified* state — exactly the
    pick the round itself would have returned.
    """

    def one(p, Li, Vi, rp, yni, mi, pci, emi, bi, wi, ym, ys, yst, pk, po,
            act):
        nc, C, _ = pci.shape
        L2, V2, rows2, mask2, yn2 = _fantasy_append(
            p, Li, Vi, rp, yni, mi, pci, pk, po, s0=s0, liar=liar)
        em2 = emi.at[pk // C, pk % C].set(True)
        sel = lambda a, b: jnp.where(act, a, b)
        L2, V2 = sel(L2, Li), sel(V2, Vi)
        rows2, mask2 = sel(rows2, rp), sel(mask2, mi)
        yn2, em2 = sel(yn2, yni), sel(em2, emi)
        if return_pick:
            beta2 = _train_beta(L2, yn2)
            if fused:
                nxt = _fused_rescore(p, L2, V2, rows2, mask2, pci, em2, bi,
                                     wi, ym, ys, yst, beta2)
            else:
                nxt = _select_chunks(p, beta2, yst, V2, ym, ys, em2, bi, wi)
        else:
            nxt = jnp.asarray(-1, jnp.int32)
        return L2, V2, rows2, mask2, yn2, em2, nxt

    return jax.vmap(one)(params_ref, L, V, rows_pad, yn, mask, pool_c,
                         evalm_c, base, weights, y_mean, y_std, ystar, pick,
                         pos, active)


# L/V donated: one set of buffers serves the whole batched fantasy chain.
_fantasy_batch = jax.jit(_fantasy_batch_impl,
                         static_argnames=("s0", "liar", "return_pick",
                                          "fused"),
                         donate_argnames=("L", "V"))


# --------------------------------------------------------------- fleet batch
def _phase1_batch_impl(params, params_ref, pool_flat, rows_pad, y_pad, mask,
                       *, steps: int):
    """Batched warm fit + drift; x/yn stay device-resident for phase 2."""

    def one(p, pref, pf, rp, yp, mi):
        x = pf[rp] + 10.0 * mi[:, None]
        yn, y_mean, y_std = _standardize(yp, mi)
        p2 = _fit(p, x, yn, mi, steps=steps)
        return p2, _drift(p2, pref), x, yn, y_mean, y_std

    with jax.named_scope("fit"):
        return jax.vmap(one)(params, params_ref, pool_flat, rows_pad, y_pad,
                             mask)


def _refactor_select_batch_impl(params, x, mask, pool_c, base, yn, y_mean,
                                y_std, sub_rows, evalm_c, keys, weights, *,
                                s: int, select: bool = True,
                                fused: bool = False):
    def one(p, xi, mi, pci, bi, yni, ym, ys, sr, em, k, w):
        with jax.named_scope("factor"):
            L = _chol_refactor(p, xi, mi)
        if fused and select:
            nc, C, _ = pci.shape
            with jax.named_scope("frontier"):
                beta, ystar = _beta_ystar(p, L, xi, yni, ym, ys, pci, sr, k,
                                          s=s)
            with jax.named_scope("score"):
                V0 = jnp.zeros((nc, L.shape[0], L.shape[1], C), jnp.float32)
                V, nxt = round_score_auto(p, L, V0, xi, beta, ystar, pci, em,
                                          bi, ym, ys, w, s0=0,
                                          backend="pallas")
            return L, V, nxt, ystar
        with jax.named_scope("v_update"):
            _, V = jax.lax.scan(
                lambda _, pc: (None, _v_chunk_refactor(p, L, xi, pc)), None,
                pci)
        with jax.named_scope("frontier"):
            beta, ystar = _beta_ystar(p, L, xi, yni, ym, ys, pci, sr, k, s=s)
        if select:
            with jax.named_scope("score"):
                nxt = _select_chunks(p, beta, ystar, V, ym, ys, em, bi, w)
        else:
            nxt = jnp.asarray(-1, jnp.int32)
        return L, V, nxt, ystar

    return jax.vmap(one)(params, x, mask, pool_c, base, yn, y_mean, y_std,
                         sub_rows, evalm_c, keys, weights)


def _update_select_batch_impl(params_ref, L, V, x, mask, pool_c, base, yn,
                              y_mean, y_std, sub_rows, evalm_c, keys, weights,
                              *, s: int, s0: int, select: bool = True,
                              fused: bool = False):
    def one(p, Li, Vi, xi, mi, pci, bi, yni, ym, ys, sr, em, k, w):
        with jax.named_scope("factor"):
            Ln = _chol_block(p, Li, xi, mi, s0)
        if fused and select:
            with jax.named_scope("frontier"):
                beta, ystar = _beta_ystar(p, Ln, xi, yni, ym, ys, pci, sr, k,
                                          s=s)
            with jax.named_scope("score"):
                Vn, nxt = round_score_auto(p, Ln, Vi, xi, beta, ystar, pci,
                                           em, bi, ym, ys, w, s0=s0,
                                           backend="pallas")
            return Ln, Vn, nxt, ystar
        with jax.named_scope("v_update"):
            _, Vn = jax.lax.scan(
                lambda _, inp: (None, _v_chunk_block(p, Ln, inp[0], xi,
                                                     inp[1], s0)),
                None, (Vi, pci))
        with jax.named_scope("frontier"):
            beta, ystar = _beta_ystar(p, Ln, xi, yni, ym, ys, pci, sr, k, s=s)
        if select:
            with jax.named_scope("score"):
                nxt = _select_chunks(p, beta, ystar, Vn, ym, ys, em, bi, w)
        else:
            nxt = jnp.asarray(-1, jnp.int32)
        return Ln, Vn, nxt, ystar

    return jax.vmap(one)(params_ref, L, V, x, mask, pool_c, base, yn, y_mean,
                         y_std, sub_rows, evalm_c, keys, weights)


_phase1_batch = jax.jit(_phase1_batch_impl, static_argnames=("steps",))
_refactor_select_batch = jax.jit(_refactor_select_batch_impl,
                                 static_argnames=("s", "select", "fused"))
# L/V are donated: the batched block update writes into the old buckets'
# storage (same no-second-V-copy property as the sequential _round_seq).
_update_select_batch = jax.jit(_update_select_batch_impl,
                               static_argnames=("s", "s0", "select", "fused"),
                               donate_argnames=("L", "V"))


# ------------------------------------------------------------ pool mutation
def _v_chunks_fresh_impl(params_ref: GPParams, L, x, pcs):
    """Fresh V for a stack of pool chunks ``pcs`` [k, C, d] → [k, m, P, C]
    under the current factorization — the dirty-chunk path of a pool edit.
    Exactly ``_v_chunk_refactor`` per chunk, so an edited chunk's V is
    bitwise what a full refactor under the same ``params_ref`` would put
    there."""
    return jax.lax.map(lambda pc: _v_chunk_refactor(params_ref, L, x, pc),
                       pcs)


_v_chunks_fresh = jax.jit(_v_chunks_fresh_impl)
_v_chunks_fresh_batch = jax.jit(jax.vmap(_v_chunks_fresh_impl))


def _pool_scores_impl(params_ref: GPParams, L, V, y_pad, mask, ystar,
                      evalm_c, weights):
    """[nc, C] acquisition scores of every pool column under a frozen round
    state (cached V, whitened targets from the last padded batch, the
    round's frozen y*). Evaluated/pad columns score −inf."""
    yn, y_mean, y_std = _standardize(y_pad, mask)
    beta = _train_beta(L, yn)

    def step(_, inp):
        Vc, em = inp
        return None, _score_chunk(params_ref, beta, Vc, y_mean, y_std,
                                  ystar, em, weights)

    _, scores = jax.lax.scan(step, None, (V, evalm_c))
    return scores


_pool_scores_seq = jax.jit(_pool_scores_impl)
_pool_scores_batch = jax.jit(jax.vmap(_pool_scores_impl))


class _EngineBase:
    """Shared knob parsing + defaulting for the sequential and batched
    engines — one place for the warm-step formula and flag semantics, so the
    two can never silently disagree."""

    def _configure(self, *, incremental: bool, warm_start: bool | None,
                   gp_steps: int, warm_steps: int | None, drift_tol: float,
                   bucket: int, s_frontiers: int, weights) -> None:
        self.incremental = bool(incremental)
        self.warm_start = (self.incremental if warm_start is None
                           else bool(warm_start))
        self.gp_steps = int(gp_steps)
        self.warm_steps = (max(10, gp_steps // 10) if warm_steps is None
                           else int(warm_steps))
        self.drift_tol = float(drift_tol)
        self.bucket = int(bucket)
        self.s_frontiers = int(s_frontiers)
        self.weights = (None if weights is None
                        else jnp.asarray(weights, jnp.float32))
        self.stats = EngineStats()

    def _fit_schedule(self, first: bool) -> tuple[bool, int]:
        """(cold, steps) for this round's Adam fit: cold restarts use the
        full ``gp_steps`` schedule, warm resumes the short ``warm_steps``."""
        cold = first or not self.warm_start
        return cold, self.gp_steps if cold else self.warm_steps

    def _resolve_chunk(self, pool_chunk, n: int) -> int:
        """``pool_chunk`` -> concrete column-chunk size C ∈ [1, n].

        ``None`` ⇒ one chunk of the whole pool (the monolithic regime);
        ``"auto"`` ⇒ :func:`repro.kernels.backend.auto_chunk`'s memory-budget
        heuristic. Any choice selects bit-identical candidates — chunking
        changes the execution schedule, never the math.
        """
        if pool_chunk is None:
            return n
        if not self.incremental:
            raise ValueError(
                "pool_chunk requires incremental=True: the exact historical "
                "path scores the pool monolithically by definition")
        if pool_chunk == "auto":
            return auto_chunk(n)
        c = int(pool_chunk)
        if c < 1:
            raise ValueError(f"pool_chunk must be >= 1, got {pool_chunk}")
        return min(c, n)

    def _setup_chunks(self, pool_chunk) -> None:
        """Build the chunk grid over ``self.pool`` ([N, d], or [S, N, d] for
        the batched engine): resolves ``pool_chunk``, pads the pool to
        ``nc·C`` with copies of row 0 (pad columns are always masked — see
        ``_evalm_chunks``) and stores the chunked view + per-chunk global
        column offsets. One implementation for both engines so the pad/grid
        conventions can never diverge."""
        n = self.pool.shape[-2]
        self._C = self._resolve_chunk(pool_chunk, n)
        self._regrid()

    def _regrid(self) -> None:
        """(Re)build the chunk grid from ``self.pool`` under the already-
        resolved chunk size ``self._C``. Pool edits call this directly —
        the chunk size is part of the engine's identity (and of any live V
        cache), so appends may add chunks but never re-resolve C."""
        n = self.pool.shape[-2]
        self._nc = -(-n // self._C)
        self._N_pad = self._nc * self._C
        pad = self._N_pad - n
        pool = self.pool
        if pad:
            reps = (1,) * (pool.ndim - 2) + (pad, 1)
            pool = jnp.concatenate(
                [pool, jnp.tile(pool[..., :1, :], reps)], axis=-2)
        self._pool_c = pool.reshape(
            pool.shape[:-2] + (self._nc, self._C, pool.shape[-1]))
        base = jnp.arange(self._nc, dtype=jnp.int32) * self._C
        self._base = (base if pool.ndim == 2
                      else jnp.tile(base, (pool.shape[0], 1)))

    def _evalm_chunks(self) -> jnp.ndarray:
        """Chunked never-re-evaluate mask ([nc, C], or [S, nc, C] batched);
        pad columns are always masked."""
        em = self._eval_mask
        pad = self._N_pad - em.shape[-1]
        if pad:
            em = jnp.concatenate(
                [em, jnp.ones(em.shape[:-1] + (pad,), bool)], axis=-1)
        return em.reshape(em.shape[:-1] + (self._nc, self._C))

    # ------------------------------------------------------ pool mutation
    # The mutable-pool contract (docs/surrogate.md): evaluated rows are the
    # engine's observation keys — `pool_replace` REFUSES to touch them, so a
    # row index, once evaluated, refers to the same design forever and the
    # never-re-evaluate mask / driver-side caches keyed by row stay valid.
    # Unevaluated columns may be replaced and new columns appended; every
    # edit stamps fresh stable ids (`candidate_ids`) so external
    # content-keyed state (proposer dedup, eval memos) can tell an edited
    # column from the candidate that previously occupied its index.

    def _init_pool_ids(self) -> None:
        self._ids = np.arange(self.N, dtype=np.int64)
        self._next_id = int(self.N)
        self._pool_edited = False

    @property
    def candidate_ids(self) -> np.ndarray:
        """Stable per-column ids [N]: assigned at construction, fresh ids on
        every appended/replaced column, preserved by ``state_dict``."""
        return self._ids.copy()

    def _check_cols(self, cols, what: str) -> jnp.ndarray:
        cols = jnp.asarray(cols, jnp.float32)
        want = self.pool.ndim
        ok = cols.ndim == want and cols.shape[-1] == self.d and (
            want == 2 or cols.shape[0] == self.S)
        if not ok:
            lead = "[k, d]" if want == 2 else "[S, k, d]"
            raise ValueError(
                f"{what}: expected columns shaped {lead} with d={self.d}"
                + ("" if want == 2 else f", S={self.S}")
                + f", got {tuple(cols.shape)}")
        return cols

    def pool_append(self, cols) -> np.ndarray:
        """Append candidate columns ([k, d], batched [S, k, d]) to the pool;
        returns their new row indices [k].

        Appends never disturb existing rows, so evaluated-row indices,
        snapshots and row-keyed caches stay valid. The chunk grid keeps its
        resolved chunk size C (the pool may gain chunks); with a live
        incremental factorization only the V chunks whose column content
        changed — the old partial tail chunk plus the new chunks — are
        recomputed (``_v_chunk_refactor`` per dirty chunk, O(m·P²·C) each;
        never a full O(P³) refactor)."""
        self._check_live()
        cols = self._check_cols(cols, "pool_append")
        k = int(cols.shape[-2])
        if k == 0:
            return np.empty((0,), np.int64)
        n_old = self.N
        self.pool = jnp.concatenate([self.pool, cols], axis=-2)
        self.N = int(self.pool.shape[-2])
        self._ids = np.concatenate([
            self._ids,
            np.arange(self._next_id, self._next_id + k, dtype=np.int64)])
        self._next_id += k
        self._pool_edited = True
        grow = jnp.zeros(self._eval_mask.shape[:-1] + (k,), bool)
        self._eval_mask = jnp.concatenate([self._eval_mask, grow], axis=-1)
        self._regrid()
        self._refresh_v(list(range(n_old // self._C, self._nc)))
        self.stats.pool_appends += k
        return np.arange(n_old, self.N, dtype=np.int64)

    def pool_replace(self, rows, cols) -> None:
        """Replace the UNEVALUATED pool columns at ``rows`` [k] with new
        candidates ([k, d], batched [S, k, d] — per-scenario encodings of
        the same k designs).

        Raises if any target row has been evaluated (in any scenario):
        evaluated rows are observation keys and must keep their content.
        Replaced columns get fresh stable ids; with a live factorization
        only the V chunks covering the edited columns are recomputed."""
        self._check_live()
        rows = np.asarray(rows, np.int64).reshape(-1)
        cols = self._check_cols(cols, "pool_replace")
        if int(cols.shape[-2]) != len(rows):
            raise ValueError(f"pool_replace: {len(rows)} rows but "
                             f"{int(cols.shape[-2])} replacement columns")
        if len(rows) == 0:
            return
        if rows.min() < 0 or rows.max() >= self.N:
            raise ValueError(f"pool_replace: row indices must be in "
                             f"[0, {self.N}), got {rows.tolist()}")
        if len(np.unique(rows)) != len(rows):
            raise ValueError("pool_replace: duplicate target rows")
        ev_any = np.asarray(self._eval_mask).reshape(-1, self.N).any(0)
        bad = rows[ev_any[rows]]
        if bad.size:
            raise ValueError(
                f"pool_replace: rows {bad.tolist()} have been evaluated — "
                "evaluated rows are observation keys and can never be "
                "replaced (append instead)")
        if self.pool.ndim == 2:
            self.pool = self.pool.at[rows].set(cols)
        else:
            self.pool = self.pool.at[:, rows].set(cols)
        self._ids[rows] = np.arange(self._next_id,
                                    self._next_id + len(rows),
                                    dtype=np.int64)
        self._next_id += len(rows)
        self._pool_edited = True
        dirty = {int(r) // self._C for r in rows}
        if 0 in rows and self._N_pad > self.N:
            dirty.add(self._nc - 1)  # pad columns are copies of row 0
        self._regrid()
        self._refresh_v(sorted(dirty))
        self.stats.pool_replacements += len(rows)

    def _refresh_v(self, dirty: list) -> None:
        """Recompute the V-cache chunks in ``dirty`` under the CURRENT
        factorization (params_ref, L) — the per-column-chunk invalidation
        that makes pool edits O(dirty·m·P²·C) instead of a refactor. Rows
        [0, s0) of a refreshed chunk are bitwise what a full refactor under
        the same params_ref would give (forward substitution row i depends
        only on rows ≤ i); the trailing rows are recomputed by the next
        round either way."""
        if self._state is None or not dirty:
            return
        st = self._state
        V = st.V
        nc_have = V.shape[-4]
        if nc_have != self._nc:  # appends added chunks
            grow = V.shape[:-4] + (self._nc - nc_have,) + V.shape[-3:]
            V = jnp.concatenate([V, jnp.zeros(grow, V.dtype)], axis=-4)
        if self._last_batch is None:
            self._state = st._replace(V=V)
            return
        rows_pad, _, mask = self._last_batch
        didx = jnp.asarray(np.asarray(dirty, np.int64))
        if self.pool.ndim == 2:
            pool_flat = self._pool_c.reshape(self._N_pad, self.d)
            x = (pool_flat[jnp.asarray(rows_pad)]
                 + 10.0 * jnp.asarray(mask)[:, None])
            fresh = _v_chunks_fresh(st.params_ref, st.L, x,
                                    self._pool_c[didx])
            V = V.at[didx].set(fresh)
        else:
            pool_flat = self._pool_c.reshape(self.S, self._N_pad, self.d)
            x = jax.vmap(lambda pf, rp, mi: pf[rp] + 10.0 * mi[:, None])(
                pool_flat, jnp.asarray(rows_pad), jnp.asarray(mask))
            fresh = _v_chunks_fresh_batch(st.params_ref, st.L, x,
                                          self._pool_c[:, didx])
            V = V.at[:, didx].set(fresh)
        self._state = st._replace(V=V)
        self.stats.v_chunk_refreshes += len(dirty)

    def pool_scores(self) -> np.ndarray:
        """Acquisition scores of every pool column — [N] (sequential) /
        [S, N] (batched) — under the LAST round's frozen state: cached V,
        whitened targets of the last padded batch and the round's frozen
        y*. Evaluated columns score −inf. The between-round proposer ranks
        replacement victims with this; it reuses the cached state, so it
        costs one O(m·P·N) scoring pass and perturbs no trajectory."""
        self._check_live()
        if not self.incremental:
            raise RuntimeError(
                "pool_scores() requires incremental=True: the exact "
                "historical path keeps no V cache to score from")
        if (self._state is None or self._last_ystar is None
                or self._last_batch is None):
            raise RuntimeError(
                "pool_scores() requires a completed round (no frozen "
                "state yet — call select/select_q first)")
        st = self._state
        rows_pad, y_pad, mask = self._last_batch
        evalm = self._evalm_chunks()
        if self.pool.ndim == 2:
            weights = (jnp.ones((self.m,), jnp.float32)
                       if self.weights is None else self.weights)
            sc = _pool_scores_seq(st.params_ref, st.L, st.V,
                                  jnp.asarray(y_pad), jnp.asarray(mask),
                                  self._last_ystar, evalm, weights)
            return np.asarray(sc).reshape(-1)[: self.N]
        weights = (jnp.ones((self.S, self.m), jnp.float32)
                   if self.weights is None else self.weights)
        sc = _pool_scores_batch(st.params_ref, st.L, st.V,
                                jnp.asarray(y_pad), jnp.asarray(mask),
                                self._last_ystar, evalm, weights)
        return np.asarray(sc).reshape(self.S, -1)[:, : self.N]

    # --------------------------------------------------- lifecycle hooks
    def _check_live(self) -> None:
        if getattr(self, "_released", False):
            raise RuntimeError(
                "engine has been released: its device arrays are gone. "
                "Build a fresh engine and load_state_dict() a snapshot "
                "taken BEFORE release() to continue this trajectory")

    def device_bytes(self) -> int:
        """Approximate byte footprint of the engine's persistent arrays
        (chunked pool, never-re-evaluate mask, incremental Cholesky/V
        caches, last padded batch and frozen y*) — exactly what
        :meth:`release` frees. The tuning server uses this to account for
        evicted-vs-resident job engines."""
        if getattr(self, "_released", False):
            return 0
        leaves = jax.tree_util.tree_leaves(
            (self._pool_c, self._eval_mask, self._state,
             self._last_batch, self._last_ystar))
        return sum(int(getattr(a, "nbytes", 0)) for a in leaves)

    def release(self) -> None:
        """Evict this engine: drop every persistent device array and make
        further observe/select/state_dict calls fail loudly. Preempting a
        job must not keep its O(N) pool state resident — the owner takes
        ``state_dict()`` first (the checkpoint), releases, and later
        rebuilds a fresh engine via ``load_state_dict``. Idempotent."""
        self._released = True
        self._state = None
        self._last_params = None
        self._last_batch = None
        self._last_ystar = None
        self._eval_mask = None
        self._pool_c = None
        self.pool = None

    # -------------------------------------------- state (de)serialization
    def _base_state_dict(self) -> dict:
        self._check_live()
        d = {
            "format": ENGINE_STATE_FORMAT,
            "kind": type(self).__name__,
            "incremental": self.incremental,
            "bucket": self.bucket,
            "pool_shape": list(self.pool.shape),
            "P": self._P,
            "n_at_last_select": self._n_at_last_select,
            "stats": self.stats.as_dict(),
        }
        if self._state is not None:
            d["state"] = {
                "params": _params_to_np(self._state.params),
                "params_ref": _params_to_np(self._state.params_ref),
                "L": np.asarray(self._state.L),
                "V": np.asarray(self._state.V),
            }
        if self._last_params is not None:
            d["last_params"] = _params_to_np(self._last_params)
        if self._last_batch is not None:
            rp, yp, mk = self._last_batch
            d["last_batch"] = {"rows_pad": np.asarray(rp),
                               "y_pad": np.asarray(yp),
                               "mask": np.asarray(mk)}
        if self._last_ystar is not None:
            d["last_ystar"] = np.asarray(self._last_ystar)
        if self._pool_edited:
            # Only edited engines carry this block, so snapshots of
            # fixed-pool runs stay byte-compatible with earlier formats.
            # The pool content is authoritative: resume must rebuild the
            # engine on the LIVE (edited) pool, and C is pinned because the
            # grid can no longer be re-derived from the construction pool.
            d["pool_edit"] = {
                "pool": np.asarray(self.pool),
                "ids": np.asarray(self._ids),
                "next_id": int(self._next_id),
                "C": int(self._C),
            }
        return d

    def _load_base_state_dict(self, d: dict) -> None:
        if d.get("format") != ENGINE_STATE_FORMAT:
            raise ValueError(
                f"engine snapshot format {d.get('format')!r} is not the "
                f"supported format {ENGINE_STATE_FORMAT}")
        if d.get("kind") != type(self).__name__:
            raise ValueError(f"snapshot was taken from a {d.get('kind')!r}, "
                             f"not a {type(self).__name__}")
        for key in ("incremental", "bucket"):
            if d.get(key) != getattr(self, key):
                raise ValueError(
                    f"snapshot {key}={d.get(key)!r} does not match this "
                    f"engine's {key}={getattr(self, key)!r}")
        if list(d.get("pool_shape", [])) != list(self.pool.shape):
            raise ValueError(
                f"snapshot pool shape {d.get('pool_shape')} does not match "
                f"this engine's pool {list(self.pool.shape)} — resume must "
                "use the identical candidate pool")
        pe = d.get("pool_edit")
        if pe is not None:
            if not np.array_equal(np.asarray(pe["pool"], np.float32),
                                  np.asarray(self.pool)):
                raise ValueError(
                    "snapshot was taken after pool edits and its pool "
                    "content does not match this engine's pool — rebuild "
                    "the engine on the live (edited) pool the driver "
                    "checkpointed alongside this snapshot")
            self._ids = np.asarray(pe["ids"], np.int64).copy()
            self._next_id = int(pe["next_id"])
            self._pool_edited = True
            if int(pe["C"]) != self._C:
                # the snapshot's chunk size was resolved against the
                # original pool; re-grid so the V validation below (and
                # every later round) uses the stored grid
                self._C = int(pe["C"])
                self._regrid()
        self._P = int(d["P"])
        self._n_at_last_select = int(d["n_at_last_select"])
        self.stats = EngineStats.from_dict(d["stats"])
        if "state" in d:
            st = d["state"]
            V = np.asarray(st["V"])
            # [nc, m, P, C] (sequential) or [S, nc, m, P, C] (batched): the
            # chunk grid is part of the stored state, so a mismatched
            # pool_chunk (e.g. "auto" resolving differently on this host)
            # must fail here with a real message, not as a shape error
            # inside the next round's jit.
            if V.shape[-1] != self._C or V.shape[-4] != self._nc:
                raise ValueError(
                    f"snapshot V cache has chunk grid nc={V.shape[-4]}, "
                    f"C={V.shape[-1]} but this engine resolved nc="
                    f"{self._nc}, C={self._C} — resume with the pool_chunk "
                    "the snapshot was taken with")
            self._state = EngineState(
                _params_from_np(st["params"]),
                _params_from_np(st["params_ref"]),
                jnp.asarray(st["L"], jnp.float32),
                jnp.asarray(V, jnp.float32))
        else:
            self._state = None
        self._last_params = (_params_from_np(d["last_params"])
                             if "last_params" in d else None)
        # Frozen state of the last completed round: pool_scores() (the
        # between-round proposer's victim ranking) must work right after a
        # resume, BEFORE this process has run a select of its own.
        lb = d.get("last_batch")
        self._last_batch = (None if lb is None else
                            (np.asarray(lb["rows_pad"]),
                             np.asarray(lb["y_pad"]),
                             np.asarray(lb["mask"])))
        self._last_ystar = (None if d.get("last_ystar") is None
                            else jnp.asarray(d["last_ystar"]))


# ============================================================== sequential
class BOEngine(_EngineBase):
    """Persistent surrogate + acquisition engine for one scenario.

    Drive it with the Alg. 3 skeleton::

        engine = BOEngine(pool_icd, gp_steps=150)
        engine.observe(init_rows, y_init)          # raw (minimized) metrics
        for _ in range(T):
            nxt = engine.select(k_acq, sub_rows)   # one BO round
            engine.observe([nxt], flow(pool_idx[nxt][None]))

    ``incremental=False`` runs the historical from-scratch round (cold
    ``fit_gp`` + ``imoo_scores`` + host argmax) and reproduces the seed
    ``soc_tuner`` trajectory bit-for-bit; see the module docstring for what
    the incremental path changes and the update/refactor policy.

    ``pool_chunk`` (``None`` | int | ``"auto"``) streams every O(N) pool
    stage in column chunks of that many candidates — same selections at any
    chunk size, peak pool-stage memory O(m·P·C) instead of O(m·P·N) — which
    is what makes 10⁵–10⁶-candidate pools practical (``docs/scaling.md``).
    At that scale always pass ``sub_rows`` to :meth:`select`: the default
    frontier subset is the whole pool, and the joint frontier draw is O(q³).
    """

    #: jitted program launches of one exact-path round (fit, posterior cache,
    #: frontier sampling, predict, scoring) — used for the stats counter.
    EXACT_DISPATCHES_PER_ROUND = 5

    def __init__(self, pool_icd, *, incremental: bool = True,
                 warm_start: bool | None = None, gp_steps: int = 150,
                 warm_steps: int | None = None, drift_tol: float = 1.0,
                 bucket: int = PAD_BUCKET, s_frontiers: int = 10,
                 weights=None, pool_chunk: int | str | None = None):
        self.pool = jnp.asarray(pool_icd, jnp.float32)      # [N, d], once
        self.N, self.d = self.pool.shape
        self._configure(incremental=incremental, warm_start=warm_start,
                        gp_steps=gp_steps, warm_steps=warm_steps,
                        drift_tol=drift_tol, bucket=bucket,
                        s_frontiers=s_frontiers, weights=weights)
        self._setup_chunks(pool_chunk)

        self._rows: list[int] = []
        self._y: np.ndarray | None = None       # [k, m] raw minimized metrics
        self._init_pool_ids()
        self._eval_mask = jnp.zeros((self.N,), bool)
        self._state: EngineState | None = None
        self._last_params: GPParams | None = None   # exact-path warm start
        self._P = 0                              # current padded train size
        self._n_at_last_select = 0
        self._last_batch = None                  # (rows_pad, y_pad, mask)
        self._last_ystar = None                  # frozen y* of the last round

    # ------------------------------------------------------------- observe
    def observe(self, rows, y) -> None:
        """Append flow evaluations: pool rows + raw (minimized) metrics."""
        self._check_live()
        rows = [int(r) for r in np.asarray(rows).reshape(-1)]
        y = np.atleast_2d(np.asarray(y, np.float32))
        if len(rows) != y.shape[0]:
            raise ValueError(f"observe: {len(rows)} rows but {y.shape[0]} metric rows")
        if not rows:
            return
        with span("engine.observe"):
            self._rows.extend(rows)
            self._y = (y if self._y is None
                       else np.concatenate([self._y, y], 0))
            self._eval_mask = self._eval_mask.at[np.asarray(rows)].set(True)

    @property
    def m(self) -> int:
        if self._y is None:
            raise RuntimeError("engine has no observations yet")
        return self._y.shape[1]

    # -------------------------------------------------------------- select
    def select(self, key, sub_rows=None) -> int:
        """Run one BO round and return the next pool row to evaluate.

        ``sub_rows`` (optional [q] int) restricts the O(q³) joint frontier
        sampling, exactly like ``imoo_scores``'s ``frontier_cand``.
        """
        self._check_live()
        if self._y is None or not self._rows:
            raise RuntimeError("select() before observe(): nothing to fit")
        if self.incremental:
            return self._select_incremental(key, sub_rows)
        return self._select_exact(key, sub_rows)

    def select_q(self, key, q: int = 1, sub_rows=None, *,
                 pending: Sequence[int] = (),
                 fantasy: str = "mean") -> list[int]:
        """Select ``q`` distinct candidates in one round via fantasy updates.

        After the round's first pick, the pick's outcome is *imputed*
        (``fantasy`` ∈ ``FANTASY_MODES``: posterior mean or a constant liar),
        pushed through the rank-1 trailing Cholesky + V-cache block update,
        the pool is re-scored and the next candidate picked — q picks for one
        GP fit. ``pending`` lists pool rows whose real evaluations are still
        in flight (an async driver's previous picks): they are fantasized
        before any new pick, so a round never re-proposes or ignores them.
        The sampled frontier maxima y* are drawn ONCE per call, by the round
        phase, and **frozen across the whole fantasy chain** (standard MES
        q-batch practice): every re-score reuses that sample, so a refill
        pays exactly one O(q³) joint frontier draw however many picks or
        pending rows it processes.

        ``q=1`` with no ``pending`` delegates to :meth:`select` and is
        therefore bit-identical to today's round. Fantasy rows only occupy
        the trailing pad region the next real round recomputes, so no
        fantasy value ever contaminates real posterior math.
        """
        self._check_live()
        pending = [int(r) for r in pending]
        if q < 1:
            raise ValueError(f"select_q: q must be >= 1, got {q}")
        if fantasy not in FANTASY_MODES:
            raise ValueError(f"select_q: fantasy must be one of "
                             f"{FANTASY_MODES}, got {fantasy!r}")
        if q == 1 and not pending:
            return [self.select(key, sub_rows)]
        if not self.incremental:
            raise ValueError(
                "q-batch / pending fantasy selection requires "
                "incremental=True: fantasy appends reuse the incremental "
                "engine's trailing Cholesky + V-cache updates")
        if self._y is None or not self._rows:
            raise RuntimeError("select_q() before observe(): nothing to fit")
        n_fant = len(pending) + q - 1
        if len(set(self._rows)) + len(pending) + q > self.N:
            raise ValueError("select_q: pool has too few unevaluated rows "
                             f"for q={q} with {len(pending)} pending")

        # Round phase: warm fit + update-or-refactor + ONE frontier sample
        # (+ first pick when there is nothing pending). `reserve` provisions
        # pad rows for the whole fantasy chain so no append can trigger
        # bucket growth mid-round; the sampled frontier y* is FROZEN across
        # the chain — fantasy steps re-score under it instead of re-paying
        # the O(q³) joint frontier draw per pick.
        pick0 = self._select_incremental(key, sub_rows, reserve=n_fant,
                                         do_select=not pending)
        n = self._n_at_last_select
        state = self._state
        ystar = self._last_ystar
        rows_pad, y_pad, mask = self._last_batch
        rows_pad = jnp.asarray(rows_pad)
        mask_j = jnp.asarray(mask)
        yn, y_mean, y_std = _standardize(jnp.asarray(y_pad), mask_j)
        weights = (jnp.ones((self.m,), jnp.float32) if self.weights is None
                   else self.weights)
        s0 = (n // self.bucket) * self.bucket
        L, V, evalm = state.L, state.V, self._evalm_chunks()
        fused = resolve_round_backend("auto", self.N) == "pallas"

        picks: list[int] = [] if pending else [int(pick0)]
        to_append = list(pending)
        appended = 0
        try:
            with span("engine.fantasy"):
                while len(picks) < q:
                    if not to_append:
                        to_append.append(picks[-1])
                    row = to_append.pop(0)
                    need_pick = not to_append  # last append before a pick
                    L, V, rows_pad, mask_j, yn, evalm, nxt = _fantasy_step(
                        state.params_ref, L, V, rows_pad, yn, mask_j,
                        self._pool_c, evalm, self._base, weights, y_mean,
                        y_std, ystar, jnp.asarray(row, jnp.int32),
                        jnp.asarray(n + appended, jnp.int32),
                        s0=s0, liar=fantasy, return_pick=need_pick,
                        fused=fused)
                    appended += 1
                    self.stats.fantasy_steps += 1
                    self.stats.dispatches += 1
                    if need_pick:
                        picks.append(int(nxt))
        except BaseException:
            # The chain donated the live L/V buffers; a partial chain would
            # leave self._state referencing deleted storage. Drop to a cold
            # rebuild (observations are host-side, nothing is lost) so the
            # engine stays usable — checkpointable, selectable — after the
            # caller handles the error.
            self._state = None
            self._P = 0
            raise
        # Keeping the fantasy-updated L/V is sound: fantasy rows live in
        # [s0, P), exactly the region the next round's block update (or
        # refactor) recomputes — see the class docstring.
        self._state = state._replace(L=L, V=V)
        return picks

    def _select_exact(self, key, sub_rows) -> int:
        """The historical from-scratch round, call-for-call (bit-exact)."""
        rows = np.asarray(self._rows)
        P = len(rows) + (-len(rows)) % self.bucket
        with span("engine.select", path="exact", P=P):
            with span("engine.fit"):
                x_train = self.pool[rows]
                state = fit_gp(x_train, jnp.asarray(-self._y, jnp.float32),
                               steps=self.gp_steps,
                               params=(self._last_params if self.warm_start
                                       else None),
                               bucket=self.bucket)
            self._last_params = state.params
            with span("engine.score"):
                fc = (self.pool if sub_rows is None
                      else self.pool[np.asarray(sub_rows)])
                scores = imoo_scores(state, self.pool, key,
                                     s=self.s_frontiers, frontier_cand=fc,
                                     weights=self.weights)
            with span("engine.fetch"):
                scores = np.array(scores)
            scores[rows] = -np.inf  # never re-evaluate
            self.stats.rounds += 1
            self.stats.dispatches += self.EXACT_DISPATCHES_PER_ROUND
            self._n_at_last_select = len(self._rows)
            return int(np.argmax(scores))

    def _select_incremental(self, key, sub_rows, *, reserve: int = 0,
                            do_select: bool = True) -> int:
        """One incremental round. ``reserve`` extra pad rows are provisioned
        beyond the real training set so a following fantasy chain (q-batch /
        pending) never triggers bucket growth mid-round; ``do_select=False``
        runs the fit + factorization but skips the scoring scan (returns -1).
        """
        n = len(self._rows)
        P = n + reserve
        P = P + (-P) % self.bucket
        with span("engine.select", path="incremental", P=P) as sp:
            grew = P != self._P
            first = self._state is None
            with span("engine.pack"):
                rows_pad, y_pad, mask = self._padded_batch(self._rows,
                                                           self._y, P)
                sub = (np.arange(self.N, dtype=np.int32) if sub_rows is None
                       else np.asarray(sub_rows, np.int32))
                weights = (jnp.ones((self.m,), jnp.float32)
                           if self.weights is None else self.weights)
                cold, steps = self._fit_schedule(first)
                params0 = (_default_params(self.m, self.d) if cold
                           else self._state.params)
                s0 = 0 if (first or grew) else \
                    (self._n_at_last_select // self.bucket) * self.bucket
                state = self._alloc_state(params0, P, first or grew)
                evalm = self._evalm_chunks()
            with span("engine.launch"):
                fused = resolve_round_backend("auto", self.N) == "pallas"
                state, nxt, did_ref, drift, ystar = _round_seq(
                    state, rows_pad, y_pad, mask, self._pool_c, evalm,
                    self._base, jnp.asarray(sub), key, bool(first or grew),
                    self.drift_tol, weights, steps=steps, s=self.s_frontiers,
                    s0=s0, select=do_select, fused=fused)

            self._state = state
            self._P = P
            self._n_at_last_select = n
            self._last_batch = (rows_pad, y_pad, mask)
            self._last_ystar = ystar
            # the host's only waits on the round's device result
            with span("engine.fetch"):
                drift, did_ref, nxt = float(drift), bool(did_ref), int(nxt)
            sp.set(refactor=did_ref)
            self.stats.rounds += 1
            self.stats.dispatches += 1
            self.stats.frontier_resamples += 1
            self.stats.last_drift = drift
            if did_ref:
                self.stats.refactors += 1
            else:
                self.stats.block_updates += 1
            return nxt

    # ------------------------------------------------------------- helpers
    @staticmethod
    def _padded_batch(rows: list[int], y: np.ndarray, P: int):
        """Pad (rows, raw y) to P with ``gp.pad_training``'s conventions: pad
        rows repeat the last real row; the +10 x-shift happens in-dispatch
        (``pool[rows_pad] + 10·mask``). This MUST stay convention-identical
        to ``pad_training`` — pinned by
        ``tests/test_engine.py::test_engine_padding_matches_pad_training``."""
        n = len(rows)
        rows_pad = np.asarray(rows + [rows[-1]] * (P - n), np.int32)
        y_neg = -np.asarray(y, np.float32)
        y_pad = np.concatenate([y_neg, np.tile(y_neg[-1:], (P - n, 1))], 0)
        mask = np.concatenate([np.zeros(n, np.float32),
                               np.ones(P - n, np.float32)])
        return rows_pad, y_pad, mask

    def _alloc_state(self, params0: GPParams, P: int, fresh: bool) -> EngineState:
        if self._state is not None and not fresh:
            return self._state._replace(params=params0)
        m = self.m
        L = jnp.zeros((m, P, P), jnp.float32)
        V = jnp.zeros((self._nc, m, P, self._C), jnp.float32)
        # params_ref must not alias params: _round_seq donates the whole
        # state, and XLA rejects donating one buffer twice.
        ref = (jax.tree.map(lambda a: jnp.array(a, copy=True), params0)
               if self._state is None else self._state.params_ref)
        return EngineState(params0, ref, L, V)

    def refactor_residual(self) -> float:
        """max |L_incremental − L_full| under the current ``params_ref`` —
        the block-update error a full refactorization would remove. Debug /
        test hook; triggers a full O(P³) factorization."""
        if self._state is None or self._last_batch is None:
            raise RuntimeError("no incremental state yet")
        rows_pad, y_pad, mask = self._last_batch
        pool_flat = self._pool_c.reshape(self._N_pad, self.d)
        x = pool_flat[rows_pad] + 10.0 * jnp.asarray(mask)[:, None]
        L_full = _chol_refactor(self._state.params_ref, x, jnp.asarray(mask))
        return float(jnp.max(jnp.abs(self._state.L - L_full)))

    # -------------------------------------------- state (de)serialization
    def state_dict(self) -> dict:
        """Complete engine snapshot — nested dict of numpy arrays + JSON-able
        scalars. :meth:`load_state_dict` on a freshly constructed engine
        (same pool, same knobs) restores it *bit-exactly*: the next
        ``select``/``select_q`` reproduces the uninterrupted run's candidate.
        ``repro.service.checkpoint`` owns the on-disk encoding."""
        d = self._base_state_dict()
        d["rows"] = np.asarray(self._rows, np.int64)
        d["y"] = None if self._y is None else np.asarray(self._y)
        return d

    def load_state_dict(self, d: dict) -> None:
        """Restore a :meth:`state_dict` snapshot (validates format, engine
        kind, bucket/incremental flags and pool shape)."""
        self._load_base_state_dict(d)
        self._rows = [int(r) for r in np.asarray(d["rows"]).reshape(-1)]
        self._y = None if d.get("y") is None else np.asarray(d["y"], np.float32)
        self._eval_mask = jnp.zeros((self.N,), bool)
        if self._rows:
            self._eval_mask = self._eval_mask.at[
                np.asarray(self._rows)].set(True)


# ================================================================= batched
class BatchedBOEngine(_EngineBase):
    """:class:`BOEngine` with a leading scenario axis [S] — the fleet's
    backend. One vmapped program covers every scenario's round; the
    refactor-vs-update decision is PER SCENARIO (a fresh/grown padded size
    still refactors the whole fleet, but drift only refactors the scenarios
    that exceed ``drift_tol`` — a mixed fleet runs one gathered dispatch
    per group and scatters back, so one drifting scenario no longer costs
    every scenario its O(P³) factorization). The incremental path costs two
    dispatches per round (fit+drift, then update-or-refactor+select; three
    in a mixed round) instead of one. Under a ``mesh`` the decision stays
    fleet-wide: gathered sub-fleets would break the even device split.

    ``pool_chunk`` streams the pool axis exactly as in :class:`BOEngine`
    (every scenario shares the chunk grid). ``mesh`` shards the scenario
    axis over devices with ``shard_map``: scenarios are embarrassingly
    parallel, so each device runs its scenario group's vmapped round with no
    collectives and the per-round host sync is the fleet-wide drift maximum
    plus one gather of the [S] picks. ``S`` must divide evenly over the mesh
    axis (``mesh_axis``, default: the mesh's first axis); sharding requires
    ``incremental=True``.

    The exact path (``incremental=False``) reproduces the historical fleet
    rounds call-for-call: ``pad_training`` → ``fit_gp_batch`` →
    ``imoo_scores_batch`` → host-side masking and per-scenario argmax.
    """

    EXACT_DISPATCHES_PER_ROUND = 3  # fit_gp_batch, frontier+predict, scores

    def __init__(self, pool_icd, *, incremental: bool = True,
                 warm_start: bool | None = None, gp_steps: int = 150,
                 warm_steps: int | None = None, drift_tol: float = 1.0,
                 bucket: int = PAD_BUCKET, s_frontiers: int = 10,
                 weights=None, pool_chunk: int | str | None = None,
                 mesh=None, mesh_axis: str | None = None):
        self.pool = jnp.asarray(pool_icd, jnp.float32)      # [S, N, d], once
        self.S, self.N, self.d = self.pool.shape
        # weights: [S, m] per-scenario acquisition weights or None (None must
        # stay None for bit-parity with the historical imoo_scores_batch call)
        self._configure(incremental=incremental, warm_start=warm_start,
                        gp_steps=gp_steps, warm_steps=warm_steps,
                        drift_tol=drift_tol, bucket=bucket,
                        s_frontiers=s_frontiers, weights=weights)
        self._setup_chunks(pool_chunk)

        self.mesh = mesh
        self.mesh_axis = None
        self._shard_cache: dict = {}
        if mesh is not None:
            if not self.incremental:
                raise ValueError(
                    "mesh sharding requires incremental=True: the exact "
                    "historical path is host-driven per round")
            self.mesh_axis = mesh_axis or mesh.axis_names[0]
            ndev = dict(zip(mesh.axis_names,
                            mesh.devices.shape))[self.mesh_axis]
            if self.S % ndev:
                raise ValueError(
                    f"fleet size S={self.S} must divide evenly over the "
                    f"{ndev} devices of mesh axis {self.mesh_axis!r}")

        self._rows: list[list[int]] = [[] for _ in range(self.S)]
        self._ys: list[np.ndarray | None] = [None] * self.S
        self._init_pool_ids()
        self._eval_mask = jnp.zeros((self.S, self.N), bool)
        self._state: EngineState | None = None   # leading [S] axis on leaves
        self._last_params = None                 # exact-path warm start
        self._P = 0
        self._n_at_last_select = 0               # min over scenarios
        self._last_batch = None                  # [S]-stacked padded batch
        self._last_ystar = None                  # frozen y* [S, s, m]

    @property
    def m(self) -> int:
        if self._ys[0] is None:
            raise RuntimeError("engine has no observations yet")
        return self._ys[0].shape[1]

    def _dispatch(self, name: str, impl, jitted, statics: dict, *args):
        """Run a batched round stage — plainly jitted, or wrapped in
        ``shard_map`` over the scenario axis when a mesh is configured.
        Every argument and result carries a leading [S] dim, so a single
        ``PartitionSpec(mesh_axis)`` prefix shards the whole call."""
        if self.mesh is None:
            return jitted(*args, **statics)
        key = (name, tuple(sorted(statics.items())))
        fn = self._shard_cache.get(key)
        if fn is None:
            spec = PartitionSpec(self.mesh_axis)
            fn = jax.jit(jax.shard_map(
                functools.partial(impl, **statics), mesh=self.mesh,
                in_specs=spec, out_specs=spec, check_vma=False))
            self._shard_cache[key] = fn
        return fn(*args)

    # ------------------------------------------------------------- observe
    def observe(self, rows_per_scenario: Sequence, ys_per_scenario: Sequence
                ) -> None:
        """Append per-scenario evaluations (lists of rows / [k,m] metrics).
        A scenario's entry may be empty (async fleets drain unevenly)."""
        self._check_live()
        if len(rows_per_scenario) != self.S or len(ys_per_scenario) != self.S:
            raise ValueError(f"expected {self.S} per-scenario entries")
        scat_s, scat_r = [], []
        for si, (rows, y) in enumerate(zip(rows_per_scenario,
                                           ys_per_scenario)):
            rows = [int(r) for r in np.asarray(rows).reshape(-1)]
            if not rows:
                continue
            y = np.atleast_2d(np.asarray(y, np.float32))
            self._rows[si].extend(rows)
            self._ys[si] = (y if self._ys[si] is None
                            else np.concatenate([self._ys[si], y], 0))
            scat_s += [si] * len(rows)
            scat_r += rows
        if scat_r:
            self._eval_mask = self._eval_mask.at[
                np.asarray(scat_s), np.asarray(scat_r)].set(True)

    # -------------------------------------------------------------- select
    def select(self, keys, sub_rows=None) -> np.ndarray:
        """One batched BO round; returns the next row per scenario [S].

        ``keys`` [S, 2] per-scenario PRNG keys; ``sub_rows`` [S, q] optional
        per-scenario frontier subsets (None ⇒ whole pool).
        """
        self._check_live()
        if any(y is None for y in self._ys):
            raise RuntimeError("select() before observe(): nothing to fit")
        if self.incremental:
            return self._select_incremental(keys, sub_rows)
        return self._select_exact(keys, sub_rows)

    def select_q(self, keys, q: int = 1, sub_rows=None, *,
                 pending: Sequence[Sequence[int]] | None = None,
                 fantasy: str = "mean") -> np.ndarray:
        """Select ``q`` distinct candidates per scenario in one vmapped
        round via fantasy updates — the fleet twin of
        :meth:`BOEngine.select_q`. Returns an ``[S, q]`` int array.

        ``pending`` is a per-scenario sequence of row lists (in-flight
        evaluations); the lists may be ragged — shorter scenarios are
        front-padded with masked no-op steps so ONE compiled program serves
        the whole fleet. Every scenario's pending rows are fantasized before
        its new picks, and the frontier y* sampled by the round phase is
        frozen across the whole chain (one O(q³) joint draw per scenario per
        refill). ``q=1`` with nothing pending anywhere delegates to
        :meth:`select` and is bitwise-identical to today's batched round.

        Capacity: the fleet refill size is shared, so a scenario whose
        unevaluated rows run out mid-chain returns arbitrary (possibly
        repeated) picks for the surplus — numerically harmless (fantasy
        rows live in the recomputed pad region either way), but the caller
        must consume at most ``N - #evaluated - #pending`` fresh picks per
        scenario. The fleet service clamps exactly so and retires saturated
        scenarios; direct callers own the same responsibility (the
        sequential :meth:`BOEngine.select_q`, whose q picks are all
        consumed, keeps its strict capacity error instead).
        """
        self._check_live()
        pending = ([[] for _ in range(self.S)] if pending is None
                   else [[int(r) for r in p] for p in pending])
        if len(pending) != self.S:
            raise ValueError(f"select_q: pending must have {self.S} "
                             f"per-scenario entries, got {len(pending)}")
        if q < 1:
            raise ValueError(f"select_q: q must be >= 1, got {q}")
        if fantasy not in FANTASY_MODES:
            raise ValueError(f"select_q: fantasy must be one of "
                             f"{FANTASY_MODES}, got {fantasy!r}")
        if q == 1 and not any(pending):
            return np.asarray(self.select(keys, sub_rows)).reshape(
                self.S, 1)
        if not self.incremental:
            raise ValueError(
                "q-batch / pending fantasy selection requires "
                "incremental=True: fantasy appends reuse the incremental "
                "engine's trailing Cholesky + V-cache updates")
        if any(y is None for y in self._ys):
            raise RuntimeError("select_q() before observe(): nothing to fit")
        for si in range(self.S):
            if len(set(self._rows[si])) + len(pending[si]) > self.N:
                raise ValueError(
                    f"select_q: scenario {si}'s evaluated + pending rows "
                    f"exceed the pool ({len(pending[si])} pending, pool "
                    f"{self.N}) — pending must be unevaluated pool rows")
        k_max = max(len(p) for p in pending)
        n_fant = k_max + q - 1

        # Round phase: batched warm fit + update-or-refactor + ONE frontier
        # sample per scenario (frozen across the chain). ``reserve``
        # provisions pad rows for the longest chain fleet-wide.
        picks0 = self._select_incremental(keys, sub_rows, reserve=n_fant,
                                          do_select=(k_max == 0))
        state = self._state
        ystar = self._last_ystar
        rows_pad, y_pad, mask = self._last_batch
        rows_pad = jnp.asarray(rows_pad)
        mask_j = jnp.asarray(mask)
        yn, y_mean, y_std = jax.vmap(_standardize)(jnp.asarray(y_pad), mask_j)
        weights = (jnp.ones((self.S, self.m), jnp.float32)
                   if self.weights is None else self.weights)
        s0 = (self._n_at_last_select // self.bucket) * self.bucket
        L, V, evalm = state.L, state.V, self._evalm_chunks()
        fused = resolve_round_backend("auto", self.N) == "pallas"

        # Per-scenario chains, front-padded to the fleet-wide max: inactive
        # steps leave a scenario untouched, so its first pick lands on the
        # same step for every scenario and the fleet stays in lockstep.
        chains = [[None] * (k_max - len(p)) + list(p) for p in pending]
        picks: list[list[int]] = ([[] for _ in range(self.S)] if k_max
                                  else [[int(x)] for x in picks0])
        ns = np.asarray([len(r) for r in self._rows], np.int64)
        appended = np.zeros(self.S, np.int64)
        try:
            for step in range(k_max + q - 1):
                if step < k_max:
                    rows_step = [chains[si][step] for si in range(self.S)]
                else:
                    rows_step = [picks[si][-1] for si in range(self.S)]
                active = np.asarray([r is not None for r in rows_step])
                rows_arr = np.asarray(
                    [0 if r is None else int(r) for r in rows_step], np.int32)
                pos = (ns + appended).astype(np.int32)
                need_pick = step >= k_max - 1
                L, V, rows_pad, mask_j, yn, evalm, nxt = self._dispatch(
                    "fantasy", _fantasy_batch_impl, _fantasy_batch,
                    {"s0": s0, "liar": fantasy, "return_pick": need_pick,
                     "fused": fused},
                    state.params_ref, L, V, rows_pad, yn, mask_j,
                    self._pool_c, evalm, self._base, weights, y_mean, y_std,
                    ystar, jnp.asarray(rows_arr), jnp.asarray(pos),
                    jnp.asarray(active))
                appended += active
                self.stats.fantasy_steps += int(active.sum())
                self.stats.dispatches += 1
                if need_pick:
                    nxt_np = np.asarray(nxt)
                    for si in range(self.S):
                        picks[si].append(int(nxt_np[si]))
        except BaseException:
            # The chain donated the live L/V buffers; drop to a cold rebuild
            # (observations are host-side, nothing is lost) so the engine
            # stays usable after the caller handles the error.
            self._state = None
            self._P = 0
            raise
        # Fantasy rows live in [s0, P) — exactly the region the next round's
        # block update (or refactor) recomputes, so keeping them is sound.
        self._state = state._replace(L=L, V=V)
        return np.asarray(picks, np.int64)

    def _select_exact(self, keys, sub_rows) -> np.ndarray:
        n_max = max(len(r) for r in self._rows)
        P = n_max + (-n_max) % self.bucket
        xs, ys, masks, fcs = [], [], [], []
        for si in range(self.S):
            rows = np.asarray(self._rows[si])
            xp, yp, mask = pad_training(
                self.pool[si][rows],
                jnp.asarray(-self._ys[si], jnp.float32), P)
            xs.append(xp), ys.append(yp), masks.append(mask)
            fcs.append(self.pool[si] if sub_rows is None
                       else self.pool[si][np.asarray(sub_rows[si])])
        gp_states = fit_gp_batch(
            jnp.stack(xs), jnp.stack(ys), jnp.stack(masks),
            steps=self.gp_steps,
            params=self._last_params if self.warm_start else None)
        self._last_params = gp_states.params
        scores = np.asarray(imoo_scores_batch(
            gp_states, self.pool, jnp.asarray(keys), s=self.s_frontiers,
            frontier_cand=jnp.stack(fcs), weights=self.weights))
        picks = np.empty((self.S,), np.int64)
        for si in range(self.S):
            s_row = scores[si].copy()
            s_row[np.asarray(self._rows[si])] = -np.inf  # never re-evaluate
            picks[si] = int(np.argmax(s_row))
        self.stats.rounds += 1
        self.stats.dispatches += self.EXACT_DISPATCHES_PER_ROUND
        self._n_at_last_select = min(len(r) for r in self._rows)
        self._P = P
        return picks

    def _select_incremental(self, keys, sub_rows, *, reserve: int = 0,
                            do_select: bool = True) -> np.ndarray:
        """One batched incremental round. ``reserve`` extra pad rows are
        provisioned beyond the fleet-wide max train size so a following
        fantasy chain never triggers bucket growth mid-round;
        ``do_select=False`` runs fit + factorization + frontier sampling but
        skips the scoring scan (returns -1 picks)."""
        n_max = max(len(r) for r in self._rows)
        P = n_max + reserve
        P = P + (-P) % self.bucket
        grew = P != self._P
        first = self._state is None
        padded = [BOEngine._padded_batch(self._rows[si], self._ys[si], P)
                  for si in range(self.S)]
        rows_pad = np.stack([p[0] for p in padded])
        y_pad = np.stack([p[1] for p in padded])
        mask = np.stack([p[2] for p in padded])
        sub = (np.tile(np.arange(self.N, dtype=np.int32), (self.S, 1))
               if sub_rows is None else np.asarray(sub_rows, np.int32))
        weights = (jnp.ones((self.S, self.m), jnp.float32)
                   if self.weights is None else self.weights)

        cold, steps = self._fit_schedule(first)
        params0 = (jax.tree.map(
            lambda a: jnp.broadcast_to(a, (self.S,) + a.shape),
            _default_params(self.m, self.d)) if cold else self._state.params)
        state = self._alloc_state(params0, P, first or grew)

        pool_flat = self._pool_c.reshape(self.S, self._N_pad, self.d)
        params, drift, x, yn, y_mean, y_std = self._dispatch(
            "phase1", _phase1_batch_impl, _phase1_batch,
            {"steps": steps}, state.params, state.params_ref, pool_flat,
            jnp.asarray(rows_pad), jnp.asarray(y_pad), jnp.asarray(mask))
        max_drift = float(jnp.max(drift))
        s0 = 0 if (first or grew) else \
            (self._n_at_last_select // self.bucket) * self.bucket
        fused = resolve_round_backend("auto", self.N) == "pallas"
        # Per-scenario refactor decisions: a fresh/grown state (or nothing
        # reusable, s0 <= 0) refactors the whole fleet; otherwise ONLY the
        # scenarios whose drift exceeds the tolerance refactor, the rest
        # block-update. An all-or-nothing fleet takes the identical single
        # dispatch as before (the golden-pinned path); a mixed fleet runs
        # one dispatch per group and scatters the results back.
        if first or grew or s0 <= 0:
            ref_idx = np.arange(self.S)
        else:
            ref_idx = np.where(np.asarray(drift) > self.drift_tol)[0]
        upd_idx = np.setdiff1d(np.arange(self.S), ref_idx)
        if upd_idx.size == 0:
            L, V, picks, ystar = self._dispatch(
                "refactor_select", _refactor_select_batch_impl,
                _refactor_select_batch,
                {"s": self.s_frontiers, "select": do_select, "fused": fused},
                params, x, jnp.asarray(mask), self._pool_c, self._base, yn,
                y_mean, y_std, jnp.asarray(sub), self._evalm_chunks(),
                jnp.asarray(keys), weights)
            params_ref = params
            self.stats.refactors += 1
        elif ref_idx.size == 0:
            L, V, picks, ystar = self._dispatch(
                "update_select", _update_select_batch_impl,
                _update_select_batch,
                {"s": self.s_frontiers, "s0": s0, "select": do_select,
                 "fused": fused},
                state.params_ref, state.L, state.V, x, jnp.asarray(mask),
                self._pool_c, self._base, yn, y_mean, y_std,
                jnp.asarray(sub), self._evalm_chunks(), jnp.asarray(keys),
                weights)
            params_ref = state.params_ref
            self.stats.block_updates += 1
        else:
            L, V, picks, ystar, params_ref = self._mixed_round(
                state, params, x, jnp.asarray(mask), yn, y_mean, y_std,
                jnp.asarray(sub), jnp.asarray(keys), weights, ref_idx,
                upd_idx, s0=s0, do_select=do_select, fused=fused)
            self.stats.mixed_rounds += 1
            self.stats.dispatches += 1  # the group split costs one extra
        self.stats.scenario_refactors += int(ref_idx.size)
        self.stats.scenario_block_updates += int(upd_idx.size)

        self._state = EngineState(params, params_ref, L, V)
        self._P = P
        self._n_at_last_select = min(len(r) for r in self._rows)
        self._last_batch = (rows_pad, y_pad, mask)
        self._last_ystar = ystar
        self.stats.rounds += 1
        self.stats.dispatches += 2
        self.stats.frontier_resamples += 1
        self.stats.last_drift = max_drift
        return np.asarray(picks)

    def _mixed_round(self, state, params, x, mask, yn, y_mean, y_std, sub,
                     keys, weights, ref_idx, upd_idx, *, s0: int,
                     do_select: bool, fused: bool):
        """Phase 2 of a mixed-drift round: refactor the drifting scenario
        group, block-update the rest, scatter L/V/picks/y* back into fleet
        order. Each group runs the SAME vmapped program as a homogeneous
        fleet, just over a gathered sub-fleet (the donated L/V are gathered
        copies, so the live state survives an interrupt). ``params_ref``
        mixes per scenario: refactoring scenarios adopt their fresh fit,
        the others keep their reference snapshot.

        Under a mesh a gathered sub-fleet would break the scenario axis's
        even device split, so both programs run over the whole sharded
        fleet and each scenario keeps its own group's result — the same
        per-scenario values at twice the round's device work."""
        ri, ui = jnp.asarray(ref_idx), jnp.asarray(upd_idx)
        evalm = self._evalm_chunks()
        take = lambda tree, i: jax.tree.map(lambda a: a[i], tree)
        ref_args = (params, x, mask, self._pool_c, self._base, yn, y_mean,
                    y_std, sub, evalm, keys, weights)
        upd_args = (state.params_ref, state.L, state.V, x, mask,
                    self._pool_c, self._base, yn, y_mean, y_std, sub, evalm,
                    keys, weights)
        ref_st = {"s": self.s_frontiers, "select": do_select, "fused": fused}
        upd_st = dict(ref_st, s0=s0)
        if self.mesh is None:
            L_r, V_r, picks_r, ystar_r = _refactor_select_batch(
                *take(ref_args, ri), **ref_st)
            L_u, V_u, picks_u, ystar_u = _update_select_batch(
                *take(upd_args, ui), **upd_st)
        else:
            L_r, V_r, picks_r, ystar_r = take(self._dispatch(
                "refactor_select", _refactor_select_batch_impl,
                _refactor_select_batch, ref_st, *ref_args), ri)
            L_u, V_u, picks_u, ystar_u = take(self._dispatch(
                "update_select", _update_select_batch_impl,
                _update_select_batch, upd_st, *upd_args), ui)
        L = state.L.at[ri].set(L_r).at[ui].set(L_u)
        V = state.V.at[ri].set(V_r).at[ui].set(V_u)
        ystar = jnp.zeros((self.S,) + ystar_r.shape[1:], ystar_r.dtype)
        ystar = ystar.at[ri].set(ystar_r).at[ui].set(ystar_u)
        picks = np.empty((self.S,), np.int64)
        picks[ref_idx] = np.asarray(picks_r)
        picks[upd_idx] = np.asarray(picks_u)
        params_ref = jax.tree.map(
            lambda new, old: old.at[ri].set(new[ri]),
            params, state.params_ref)
        return L, V, picks, ystar, params_ref

    def _alloc_state(self, params0, P: int, fresh: bool) -> EngineState:
        if self._state is not None and not fresh:
            return self._state._replace(params=params0)
        m = self.m
        L = jnp.zeros((self.S, m, P, P), jnp.float32)
        V = jnp.zeros((self.S, self._nc, m, P, self._C), jnp.float32)
        ref = params0 if self._state is None else self._state.params_ref
        return EngineState(params0, ref, L, V)

    # -------------------------------------------- state (de)serialization
    def state_dict(self) -> dict:
        """Batched twin of :meth:`BOEngine.state_dict` — per-scenario train
        sets are ragged, so rows/targets are stored per scenario index."""
        d = self._base_state_dict()
        d["rows"] = {str(si): np.asarray(r, np.int64)
                     for si, r in enumerate(self._rows)}
        d["ys"] = {str(si): None if y is None else np.asarray(y)
                   for si, y in enumerate(self._ys)}
        return d

    def load_state_dict(self, d: dict) -> None:
        self._load_base_state_dict(d)
        self._rows = [[int(r) for r in
                       np.asarray(d["rows"][str(si)]).reshape(-1)]
                      for si in range(self.S)]
        self._ys = [None if d["ys"].get(str(si)) is None
                    else np.asarray(d["ys"][str(si)], np.float32)
                    for si in range(self.S)]
        self._eval_mask = jnp.zeros((self.S, self.N), bool)
        scat_s = [si for si, rows in enumerate(self._rows) for _ in rows]
        scat_r = [r for rows in self._rows for r in rows]
        if scat_r:
            self._eval_mask = self._eval_mask.at[
                np.asarray(scat_s), np.asarray(scat_r)].set(True)
