"""Gaussian-process surrogates (paper Eqs. 3-4), pure JAX.

One independent GP per objective (the paper combines per-objective GPs as a
stacked MVN, Eq. 3); hyperparameters θ = (ARD log-lengthscales, log-variance,
log-noise) are fit by maximizing the exact marginal likelihood with Adam
(paper Alg. 3 line 9: "θ is optimized via gradient descent").

Everything is jit-compiled and vmapped over objectives, so a 3-objective fit
is a single XLA program; predictive code paths are Cholesky-based throughout.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.kernels import backend as _backend

__all__ = ["GPParams", "GPState", "fit_gp", "fit_gp_batch", "pad_training",
           "gp_predict", "gp_joint_samples"]

JITTER = 1e-5
# jit-cache padding granularity for growing-n training sets; the fleet runner
# pads every scenario to a multiple of this so it MUST stay in sync with the
# sequential path — change it here, nowhere else.
PAD_BUCKET = 8


def _mm(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Full-f32 matmul. A TPU's default f32 matmul rounds its operands to
    bfloat16, which breaks the Cholesky/Schur-complement algebra here."""
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


class GPParams(NamedTuple):
    log_ls: jnp.ndarray  # [m, d] ARD log-lengthscales
    log_var: jnp.ndarray  # [m] log signal variance
    log_noise: jnp.ndarray  # [m] log noise variance (σ_e² in Eq. 4)


class GPState(NamedTuple):
    params: GPParams
    x: jnp.ndarray  # [n, d] training inputs (ICD space)
    y: jnp.ndarray  # [n, m] standardized targets
    y_mean: jnp.ndarray  # [m]
    y_std: jnp.ndarray  # [m]
    chol: jnp.ndarray  # [m, n, n] Cholesky of K + σ²I
    alpha: jnp.ndarray  # [m, n]  (K+σ²I)⁻¹ y


def _kernel(params_i, a: jnp.ndarray, b: jnp.ndarray,
            differentiable: bool = True) -> jnp.ndarray:
    """ARD RBF kernel for one objective.

    Routed through the unified pairdist backend (``kernels.backend``). The
    ``auto`` dispatch resolves to XLA unless ``REPRO_PAIRDIST_BACKEND``
    upgrades it (fidelity default: bit-identical to the historical inline
    ``_sqdist`` on every platform; export ``platform`` to use the Pallas
    kernel on TPU for inference-only callers). The NLL gradient path keeps
    ``differentiable=True``, which pins the XLA form unconditionally — the
    Pallas kernel has no VJP."""
    log_ls, log_var = params_i
    ls = jnp.exp(log_ls)
    d2 = _backend.pairdist_auto(a / ls[None, :], b / ls[None, :],
                                differentiable=differentiable)
    return jnp.exp(log_var) * jnp.exp(-0.5 * d2)


def _nll_one(log_ls, log_var, log_noise, x, y, mask=None) -> jnp.ndarray:
    """Exact negative log marginal likelihood for one objective."""
    n = x.shape[0]
    K = _kernel((log_ls, log_var), x, x)
    K = K + (jnp.exp(log_noise) + JITTER) * jnp.eye(n)
    if mask is not None:  # inert padded rows: effectively infinite noise
        K = K + jnp.diag(1e6 * mask)
    L = jnp.linalg.cholesky(K)
    alpha = jax.scipy.linalg.cho_solve((L, True), y)
    nll = _mm(0.5 * y, alpha) + jnp.sum(jnp.log(jnp.diagonal(L))) + 0.5 * n * jnp.log(2 * jnp.pi)
    # Weak log-normal hyperpriors keep lengthscales in a sane band when n is
    # tiny (first BO rounds) — standard practice, removable via prior_w=0.
    prior = 0.05 * (jnp.sum(log_ls**2) + log_var**2 + (log_noise + 4.0) ** 2)
    return nll + prior


@functools.partial(jax.jit, static_argnames=("steps",))
def _fit(params: GPParams, x, y, mask, steps: int = 200,
         lr: float = 5e-2) -> GPParams:
    """Adam on the summed per-objective NLL (objectives are independent, so a
    joint sum is exactly per-objective optimization)."""

    def loss(p: GPParams):
        per = jax.vmap(_nll_one, in_axes=(0, 0, 0, None, 1, None))(
            p.log_ls, p.log_var, p.log_noise, x, y, mask)
        return jnp.sum(per)

    grad_fn = jax.value_and_grad(loss)
    b1, b2, eps = 0.9, 0.999, 1e-8

    def step(carry, t):
        p, m, v = carry
        _, g = grad_fn(p)
        m = jax.tree.map(lambda mi, gi: b1 * mi + (1 - b1) * gi, m, g)
        v = jax.tree.map(lambda vi, gi: b2 * vi + (1 - b2) * gi * gi, v, g)
        mh = jax.tree.map(lambda mi: mi / (1 - b1 ** (t + 1.0)), m)
        vh = jax.tree.map(lambda vi: vi / (1 - b2 ** (t + 1.0)), v)
        p = jax.tree.map(lambda pi, mi, vi: pi - lr * mi / (jnp.sqrt(vi) + eps), p, mh, vh)
        # clamp to a numerically safe band: noiseless smooth targets push
        # noise->0 / var->inf, and the f32 Cholesky NaNs past cond ~1e7
        p = GPParams(
            log_ls=jnp.clip(p.log_ls, -3.0, 3.5),
            log_var=jnp.clip(p.log_var, -3.0, 3.0),
            log_noise=jnp.clip(p.log_noise, -7.0, 2.0),
        )
        return (p, m, v), None

    zeros = jax.tree.map(jnp.zeros_like, params)
    (params, _, _), _ = jax.lax.scan(step, (params, zeros, zeros), jnp.arange(steps))
    return params


@jax.jit
def _posterior_cache(params: GPParams, x, y, mask):
    def one(log_ls, log_var, log_noise, yi):
        n = x.shape[0]
        K = (_kernel((log_ls, log_var), x, x, differentiable=False)
             + (jnp.exp(log_noise) + JITTER) * jnp.eye(n))
        K = K + jnp.diag(1e6 * mask)
        L = jnp.linalg.cholesky(K)
        alpha = jax.scipy.linalg.cho_solve((L, True), yi)
        return L, alpha

    return jax.vmap(one, in_axes=(0, 0, 0, 1))(
        params.log_ls, params.log_var, params.log_noise, y)


def pad_training(x: jnp.ndarray, y: jnp.ndarray, bucket: int = PAD_BUCKET
                 ) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Pad (x [n,d], y [n,m]) to the next multiple of ``bucket`` with inert
    rows and return ``(x_pad, y_pad, mask)`` where ``mask`` is 1.0 on padded
    rows. Padded rows copy the last real row (shifted far away in x) and are
    silenced in the GP by a huge per-point noise — see ``_nll_one``.

    The fleet runner calls this with ``bucket`` set to the fleet-wide padded
    length so every scenario's training set lands on the same static shape.
    The incremental engine re-derives the same convention on device
    (``BOEngine._padded_batch`` + in-dispatch +10 shift); if you change the
    pad-row choice or the shift, change it there too — the parity is pinned
    by ``tests/test_engine.py::test_engine_padding_matches_pad_training``."""
    x = jnp.asarray(x, jnp.float32)
    y = jnp.asarray(y, jnp.float32)
    n = x.shape[0]
    pad = (-n) % bucket
    mask = jnp.concatenate([jnp.zeros((n,)), jnp.full((pad,), 1.0)])
    if pad:
        x = jnp.concatenate([x, jnp.tile(x[-1:], (pad, 1)) + 10.0], axis=0)
        y = jnp.concatenate([y, jnp.tile(y[-1:], (pad, 1))], axis=0)
    return x, y, mask


def _default_params(m: int, d: int) -> GPParams:
    return GPParams(
        log_ls=jnp.zeros((m, d)) - 0.5,
        log_var=jnp.zeros((m,)),
        log_noise=jnp.zeros((m,)) - 4.0,
    )


def _standardize(y: jnp.ndarray, mask: jnp.ndarray
                 ) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Per-objective standardization over REAL rows only (mask=1 on padding).

    Computing the moments under the mask makes the amount of padding inert:
    a fleet scenario padded to the fleet-wide max gets the same GP targets as
    the same data padded to its own bucket — without this, duplicated pad
    rows would bias the moments and couple scenarios through their sizes."""
    w = (1.0 - mask)[:, None]
    cnt = jnp.maximum(jnp.sum(w), 1.0)
    y_mean = jnp.sum(y * w, axis=0) / cnt
    y_std = jnp.sqrt(jnp.sum((y - y_mean) ** 2 * w, axis=0) / cnt) + 1e-9
    return (y - y_mean) / y_std, y_mean, y_std


def fit_gp(x: jnp.ndarray, y: jnp.ndarray, steps: int = 200,
           params: GPParams | None = None, bucket: int = PAD_BUCKET) -> GPState:
    """Fit m independent GPs on (x [n,d], y [n,m]); y standardized internally.

    Training sets are padded to multiples of ``bucket`` with inert rows
    (masked by a huge per-point noise) so the BO loop's growing-n refits hit
    the jit cache (O(log T) compiles instead of O(T))."""
    x, y, mask = pad_training(x, y, bucket)
    m, d = y.shape[1], x.shape[1]
    yn, y_mean, y_std = _standardize(y, mask)
    if params is None:
        params = _default_params(m, d)
    params = _fit(params, x, yn, mask, steps=steps)
    chol, alpha = _posterior_cache(params, x, yn, mask)
    return GPState(params, x, yn, y_mean, y_std, chol, alpha)


@functools.partial(jax.jit, static_argnames=("steps",))
def _fit_batch(params: GPParams, x, y, mask, steps: int):
    def one(p, xi, yi, mi):
        yn, y_mean, y_std = _standardize(yi, mi)
        p = _fit(p, xi, yn, mi, steps=steps)
        chol, alpha = _posterior_cache(p, xi, yn, mi)
        return GPState(p, xi, yn, y_mean, y_std, chol, alpha)

    return jax.vmap(one)(params, x, y, mask)


def fit_gp_batch(x: jnp.ndarray, y: jnp.ndarray, mask: jnp.ndarray,
                 steps: int = 200, params: GPParams | None = None) -> GPState:
    """Fit ``S`` independent multi-objective GPs in one vmapped XLA program.

    ``x`` [S,n,d], ``y`` [S,n,m], ``mask`` [S,n] (1.0 on inert padded rows —
    build each scenario's slice with :func:`pad_training`). Returns a
    ``GPState`` whose every field carries a leading scenario axis; feed it to
    the batched acquisition (``imoo_scores_batch``) or index scenario ``i``
    out with ``jax.tree.map(lambda a: a[i], state)``.

    Each scenario's fit is computation-for-computation identical to
    :func:`fit_gp` (same padding rule, mask-aware standardization, Adam
    schedule and hyperpriors) — a fleet of one reproduces the sequential
    trajectory."""
    x = jnp.asarray(x, jnp.float32)
    y = jnp.asarray(y, jnp.float32)
    S, _, m = y.shape
    d = x.shape[-1]
    if params is None:
        params = jax.tree.map(
            lambda a: jnp.broadcast_to(a, (S,) + a.shape), _default_params(m, d))
    return _fit_batch(params, x, y, jnp.asarray(mask, jnp.float32), steps=steps)


@jax.jit
def gp_predict(state: GPState, xq: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Posterior mean/std at query points, de-standardized. Returns ([q,m],[q,m])."""

    def one(log_ls, log_var, L, alpha):
        Ks = _kernel((log_ls, log_var), state.x, xq,
                     differentiable=False)  # [n, q]
        mean = _mm(Ks.T, alpha)
        Vs = jax.scipy.linalg.solve_triangular(L, Ks, lower=True)
        var = jnp.exp(log_var) - jnp.sum(Vs * Vs, axis=0)
        return mean, jnp.sqrt(jnp.maximum(var, 1e-10))

    mean, std = jax.vmap(one)(state.params.log_ls, state.params.log_var,
                              state.chol, state.alpha)
    return (mean.T * state.y_std + state.y_mean, std.T * state.y_std)


@functools.partial(jax.jit, static_argnames=("s",))
def gp_joint_samples(state: GPState, xq: jnp.ndarray, key: jax.Array,
                     s: int = 10) -> jnp.ndarray:
    """``s`` joint posterior samples at ``xq`` [q,d] -> [s, q, m].

    Used for Monte-Carlo Pareto-frontier sampling in the acquisition (Eq. 7):
    a joint draw needs the full q×q posterior covariance Cholesky — that is
    MXU-shaped work on TPU and the reason ``xq`` is a subsampled candidate
    set in the tuner."""

    def one(log_ls, log_var, L, alpha, k):
        q = xq.shape[0]
        Ks = _kernel((log_ls, log_var), state.x, xq,
                     differentiable=False)  # [n, q]
        Kqq = _kernel((log_ls, log_var), xq, xq, differentiable=False)
        mean = _mm(Ks.T, alpha)
        Vs = jax.scipy.linalg.solve_triangular(L, Ks, lower=True)
        cov = Kqq - _mm(Vs.T, Vs)
        # prior-scaled jitter: the f32 subtraction leaves small negative
        # eigenvalues when the posterior collapses (long lengthscales);
        # 1e-4 x prior variance dominates them at every hyperparameter
        jit = 1e-4 * jnp.exp(log_var) + 1e-6
        Lq = jnp.linalg.cholesky(cov + jit * jnp.eye(q))
        eps = jax.random.normal(k, (q, s))
        return mean[:, None] + _mm(Lq, eps)  # [q, s]

    keys = jax.random.split(key, state.y.shape[1])
    samp = jax.vmap(one)(state.params.log_ls, state.params.log_var,
                         state.chol, state.alpha, keys)  # [m, q, s]
    samp = jnp.transpose(samp, (2, 1, 0))  # [s, q, m]
    return samp * state.y_std[None, None, :] + state.y_mean[None, None, :]
