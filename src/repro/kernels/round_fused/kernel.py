"""Fused BO acquisition-round kernel (TPU Pallas; compiles for v5e).

One launch replaces the four staged pool passes of
``core/engine.py::_round_seq``'s scoring half: for every 128-wide column
tile of every pool chunk it

1. recomputes the trailing rows of the cached whitening
   ``V = L⁻¹·K(train_pad, pool)`` — the streamed pairdist block against the
   training rows plus a row-by-row forward substitution written in place
   into the V output block (``s0 = 0`` is a full refactor of the tile's V
   column, ``s0 = P`` skips the update entirely: the score-only fantasy
   re-score);
2. accumulates the posterior moments in the SAME fixed order as
   ``engine._col_moments`` (sequential ``fori_loop`` over the P train rows,
   never a width-dependent GEMV reduction — the chunk-size bit-parity of
   the engine rests on that order);
3. de-standardizes and scores the tile with the closed-form MES information
   gain (``core.acquisition.mes_information_gain``), averaged over the S
   frozen frontier samples and weighted per objective;
4. masks already-evaluated candidates to ``-inf`` and folds the tile into a
   running global argmax held in ``(1, 1)`` SMEM output blocks that every
   grid step revisits (the sequential-grid accumulation idiom of
   ``pareto_count``). Strict ``>`` keeps the earliest tile and in-tile
   ``argmax`` keeps the first column — composed over the row-major
   ``(chunk, tile)`` grid this reproduces the engine's monolithic
   first-index-wins tie semantics exactly.

Everything between the pool-chunk HBM read and the scalar pick index stays
in VMEM: no ``[P, N]`` kernel product, ``[N]`` score vector or
``[S, N, m]`` MES broadcast ever round-trips through HBM. The updated V
tile is the only O(N) output (the engine carries V across rounds).

Objective count ``m`` and frontier count ``S`` are compile-time Python
loops — both are single digits in every workload.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: pool-column tile width (TPU lane count) — the grid's inner axis.
TILE_C = 128
#: feature-axis alignment required by the raw kernel (ops pads).
LANE = 128
#: MXU precision of every dot: full f32, as the engine's XLA round uses
_F32 = jax.lax.Precision.HIGHEST

# Mosaic has no erf/erfc lowering, so the kernel evaluates the normal CDF
# with the Cephes single-precision polynomials (erf on |w| < 1, erfc
# beyond), arranged as ``jax.scipy.special.ndtr`` arranges its branches.
# Over [-12, 12] it stays within 6e-7 relative of ``jax.scipy.stats.norm.cdf``.
_ERF_T = (7.853861353153693e-5, -8.010193625184903e-4, 5.188327685732524e-3,
          -2.685381193529856e-2, 1.128358514861418e-1, -3.761262582423300e-1,
          1.128379165726710e+0)
_ERFC_P = (2.326819970068386e-2, -1.387039388740657e-1, 3.687424674597105e-1,
           -5.824733027278666e-1, 6.210004621745983e-1, -4.944515323274145e-1,
           3.404879937665872e-1, -2.741127028184656e-1, 5.638259427386472e-1)
_ERFC_R = (-1.047766399936249e+1, 1.297719955372516e+1, -7.495518717768503e+0,
           2.921019019210786e+0, -1.015265279202700e+0, 4.218463358204948e-1,
           -2.820767439740514e-1, 5.641895067754075e-1)
_HALF_SQRT2 = float(np.float32(0.5) * np.sqrt(np.float32(2.0)))


def _poly(x, coeffs):
    acc = jnp.full_like(x, coeffs[0])
    for c in coeffs[1:]:
        acc = acc * x + c
    return acc


def _ndtr(x):
    """Standard normal CDF from erf/erfc polynomials (see ``_ERF_T``)."""
    w = x * _HALF_SQRT2
    z = jnp.abs(w)
    zs = jnp.minimum(z, 1.0)
    zl = jnp.maximum(z, 1.0)
    q = 1.0 / zl
    yq = q * q
    erfc_l = jnp.exp(-zl * zl) * q * jnp.where(zl < 2.0, _poly(yq, _ERFC_P),
                                               _poly(yq, _ERFC_R))
    erfc_z = jnp.where(z > 1.0, erfc_l, 1.0 - zs * _poly(zs * zs, _ERF_T))
    y = jnp.where(z < _HALF_SQRT2, 1.0 + w * _poly(w * w, _ERF_T),
                  jnp.where(w > 0.0, 2.0 - erfc_z, erfc_z))
    return y * 0.5


def _round_body(x_ref, ls_ref, scal_ref, L_ref, beta_ref, ystar_ref, pc_ref,
                vold_ref, evalm_ref, vnew_ref, bestv_ref, besti_ref, *,
                s0: int, c_orig: int):
    j = pl.program_id(0)          # pool chunk
    t = pl.program_id(1)          # 128-wide column tile within the chunk

    @pl.when(jnp.logical_and(j == 0, t == 0))
    def _init():
        bestv_ref[0, 0] = -jnp.inf
        besti_ref[0, 0] = 0

    P = x_ref.shape[0]
    m = L_ref.shape[0]
    S = ystar_ref.shape[0]
    pc = pc_ref[0]                # [TILE_C, d]
    cols = jax.lax.broadcasted_iota(jnp.int32, (1, P), 1)
    scores = jnp.zeros((1, TILE_C), jnp.float32)
    for i in range(m):
        ls = ls_ref[i:i + 1, :]   # [1, d] ARD lengthscales (already exp'd)
        y_mean = scal_ref[0, i]   # scalars live in SMEM
        y_std = scal_ref[1, i]
        w = scal_ref[2, i]
        var_i = scal_ref[3, i]    # exp(log_var)
        v_in = vold_ref.at[0, i]  # [P, TILE_C] views
        v_out = vnew_ref.at[0, i]
        if s0 < P:
            # -- streamed pairdist block + RBF: K(x[s0:], tile)  [B, TILE_C]
            xb = x_ref[s0:, :] / ls
            pcs_t = (pc / ls).T   # [d, TILE_C]: norms reduce over sublanes
            bb = jnp.sum(xb * xb, axis=1, keepdims=True)
            cc = jnp.sum(pcs_t * pcs_t, axis=0, keepdims=True)
            cross = jnp.dot(xb, pcs_t, precision=_F32,
                            preferred_element_type=jnp.float32)
            d2 = jnp.maximum(bb + cc - 2.0 * cross, 0.0)
            # -- trailing triangular solve in place in the output block:
            # rows [:s0] keep the cached V, rows [s0:] start as K(x[s0:], ·)
            # and row r becomes (K_r − L[r, :r]·V[:r]) / L[r, r]
            if s0 > 0:
                v_out[:s0, :] = v_in[:s0, :]
            v_out[s0:, :] = var_i * jnp.exp(-0.5 * d2)

            def fwd(r, carry):
                lrow = L_ref[i, pl.ds(r, 1), :]                  # [1, P]
                acc = jnp.dot(jnp.where(cols < r, lrow, 0.0), v_out[...],
                              precision=_F32,
                              preferred_element_type=jnp.float32)
                diag = jnp.sum(jnp.where(cols == r, lrow, 0.0), axis=1,
                               keepdims=True)
                v_out[pl.ds(r, 1), :] = (v_out[pl.ds(r, 1), :] - acc) / diag
                return carry

            jax.lax.fori_loop(s0, P, fwd, 0)
            v_cur = v_out
        else:
            v_cur = v_in          # score-only: cached V is current
        # -- posterior moments, _col_moments' exact accumulation order
        def mom(p, acc):
            mu, ss = acc
            vrow = v_cur[pl.ds(p, 1), :]
            return mu + beta_ref[i, p] * vrow, ss + vrow * vrow

        v0 = v_cur[0:1, :]
        mu, ss = jax.lax.fori_loop(1, P, mom, (beta_ref[i, 0] * v0, v0 * v0))
        std = jnp.sqrt(jnp.maximum(var_i - ss, 1e-10))
        mean_d = mu * y_std + y_mean          # de-standardized
        std_d = std * y_std
        # -- MES information gain over the S frozen frontier samples
        af = jnp.zeros((1, TILE_C), jnp.float32)
        for si in range(S):
            gamma = (ystar_ref[si, i] - mean_d) / std_d
            pdf = jax.scipy.stats.norm.pdf(gamma)
            cdf = jnp.clip(_ndtr(gamma), 1e-9, 1.0)
            af = af + (gamma * pdf / (2.0 * cdf) - jnp.log(cdf))
        scores = scores + w * (af / S)
    # -- never-re-evaluate mask + running global argmax
    scores = jnp.where(evalm_ref[0] > 0.5, -jnp.inf, scores)
    local_max = jnp.max(scores)
    local_idx = jnp.argmax(scores, axis=1)[0].astype(jnp.int32)

    @pl.when(local_max > bestv_ref[0, 0])
    def _take():
        bestv_ref[0, 0] = local_max
        besti_ref[0, 0] = j * c_orig + t * TILE_C + local_idx


def round_fused(x, ls, scal, L, beta, ystar, pool_c, v_old, evalm, *,
                s0: int, c_orig: int | None = None, interpret: bool = False):
    """Raw fused round kernel — tile-aligned shapes required (use
    ``ops.round_select`` for arbitrary shapes).

    Args: ``x`` [P, d] padded train rows; ``ls`` [m, d] lengthscales
    (``exp(log_ls)``); ``scal`` [4, m] rows = (y_mean, y_std, weights,
    ``exp(log_var)``); ``L`` [m, P, P]; ``beta`` [m, P]; ``ystar`` [S, m];
    ``pool_c`` [nc, C, d]; ``v_old`` [nc, m, P, C]; ``evalm`` [nc, C] bool.
    ``s0`` rows of V are reused; ``s0 >= P`` scores the cached V without
    updating it. ``c_orig`` is the UNPADDED chunk width the global pick
    index is built from (defaults to C).

    Returns ``(v_new [nc, m, P, C], best_idx [1,1] int32)``.
    """
    nc, C, d = pool_c.shape
    m, P, _ = L.shape
    S = ystar.shape[0]
    if C % TILE_C:
        raise ValueError(f"C={C} must be a multiple of TILE_C={TILE_C}")
    if d % LANE:
        raise ValueError(f"D={d} must be a multiple of LANE={LANE}")
    if x.shape != (P, d) or ls.shape != (m, d):
        raise ValueError(f"x/ls feature dims must match pool: x={x.shape}, "
                         f"ls={ls.shape}, pool d={d}, P={P}")
    if v_old.shape != (nc, m, P, C):
        raise ValueError(f"v_old shape {v_old.shape} != {(nc, m, P, C)}")
    s0 = int(s0)
    write_v = s0 < P
    # the mask rides as f32 [nc, 1, C] so its (1, TILE_C) block tiles for
    # any nc; scalars, whitened targets and frontier samples go to SMEM
    evalm = evalm.astype(jnp.float32).reshape(nc, 1, C)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    out_shape = [jax.ShapeDtypeStruct((nc, m, P, C), jnp.float32),
                 jax.ShapeDtypeStruct((1, 1), jnp.float32),
                 jax.ShapeDtypeStruct((1, 1), jnp.int32)]
    v_new, _, best_idx = pl.pallas_call(
        functools.partial(_round_body, s0=min(s0, P),
                          c_orig=int(C if c_orig is None else c_orig)),
        grid=(nc, C // TILE_C),
        in_specs=[
            pl.BlockSpec((P, d), lambda j, t: (0, 0)),          # x
            pl.BlockSpec((m, d), lambda j, t: (0, 0)),          # ls
            smem,                                               # scalars
            pl.BlockSpec((m, P, P), lambda j, t: (0, 0, 0)),    # L
            smem,                                               # beta
            smem,                                               # ystar
            pl.BlockSpec((1, TILE_C, d), lambda j, t: (j, t, 0)),
            pl.BlockSpec((1, m, P, TILE_C), lambda j, t: (j, 0, 0, t)),
            pl.BlockSpec((1, 1, TILE_C), lambda j, t: (j, 0, t)),
        ],
        out_specs=[
            pl.BlockSpec((1, m, P, TILE_C), lambda j, t: (j, 0, 0, t)),
            smem,                                               # running max
            smem,                                               # running idx
        ],
        out_shape=out_shape,
        interpret=interpret,
    )(x, ls, scal, L, beta, ystar, pool_c, v_old, evalm)
    if not write_v:
        v_new = v_old
    return v_new, best_idx
