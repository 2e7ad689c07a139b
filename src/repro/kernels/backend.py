"""Unified kernel backend: one dispatch point per kernel family.

Every consumer of pairwise squared distances / RBF kernel matrices — the GP
surrogate's ARD kernel (``core.gp``), TED initialization (``core.sampling``)
and, through the GP, the IMOO acquisition — routes through
:func:`pairdist_auto` instead of picking an implementation inline; Pareto
dominance counting (``core.pareto``) routes through
:func:`dominance_counts_auto`, batched SoC cost-model evaluation
(``soc.flow.VLSIFlow``) through :func:`soc_metrics_auto`, and the BO
engine's fused acquisition round (``core.engine``) through
:func:`round_score_auto`, each under the same dispatch rules with its own
environment override (``REPRO_PARETO_BACKEND`` / ``REPRO_SYSTOLIC_BACKEND``
/ ``REPRO_ROUND_BACKEND``). Dispatch:

* ``"auto"``     — the ``REPRO_PAIRDIST_BACKEND`` environment variable if
  set (``xla`` / ``pallas`` / ``platform``), else ``"xla"``. XLA is the
  *fidelity default* on every platform: it is bit-identical to the
  historical inline implementations (``gp._sqdist`` /
  ``sampling.pairwise_sqdist``), so unchanged flags ⇒ unchanged
  trajectories — on TPU too. Export ``REPRO_PAIRDIST_BACKEND=platform`` to
  upgrade every ``auto`` call site at once.
* ``"platform"`` — the Pallas kernel on TPU for tile-worthy shapes, plain
  XLA everywhere else (off-TPU the Pallas path only exists in interpret
  mode, which is a correctness tool, not a fast path);
* ``"pallas"``   — force the Pallas kernel (interpret-mode off-TPU), behind
  the pad-to-tile / slice-back wrapper so callers never see the raw
  kernel's tile-multiple shape requirements;
* ``"xla"``      — the ``‖a‖²+‖b‖²−2ab`` form. Also the only legal choice
  under autodiff: the Pallas kernel has no VJP, so differentiated callers
  (the GP's NLL gradient) pass ``differentiable=True``.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp

from .common import pad_to, use_interpret
from .pairdist.kernel import LANE, TILE_I, TILE_J, pairdist as _raw_pairdist

__all__ = ["pairdist_auto", "pairdist_chunked", "auto_chunk",
           "resolve_backend", "sqdist_xla", "rbf_xla",
           "dominance_counts_auto", "resolve_pareto_backend",
           "dominance_counts_xla",
           "soc_metrics_auto", "resolve_systolic_backend",
           "round_score_auto", "resolve_round_backend"]

_ENV_VAR = "REPRO_PAIRDIST_BACKEND"
_PARETO_ENV_VAR = "REPRO_PARETO_BACKEND"
_SYSTOLIC_ENV_VAR = "REPRO_SYSTOLIC_BACKEND"
_ROUND_ENV_VAR = "REPRO_ROUND_BACKEND"
_BACKENDS = ("auto", "platform", "pallas", "xla")


def _resolve(kind: str, env_var: str, backend: str, tile_ok) -> str:
    """Shared resolver behind every ``resolve_*_backend``: env-var parse →
    validate → explicit pallas/xla passthrough → off-TPU ⇒ XLA →
    tile-worthiness check (``tile_ok`` is lazy — kernel tile constants are
    only imported when a TPU ``platform`` resolution actually needs them)."""
    if backend == "auto":
        backend = os.environ.get(env_var, "xla")  # fidelity default
    if backend not in _BACKENDS:
        raise ValueError(
            f"unknown {kind} backend {backend!r}; expected one of {_BACKENDS}")
    if backend in ("pallas", "xla"):
        return backend
    if jax.default_backend() != "tpu":
        return "xla"
    return "pallas" if tile_ok() else "xla"

#: default streaming budget for :func:`auto_chunk` (MB of f32 working set
#: per column block) — small enough to stay cache-resident on a CPU host,
#: large enough that per-chunk dispatch overhead is negligible.
DEFAULT_CHUNK_BUDGET_MB = 64


def resolve_backend(backend: str = "auto", n: int | None = None,
                    m: int | None = None) -> str:
    """Resolve ``"auto"``/``"platform"`` to a concrete backend for an
    [n,·]×[m,·] problem (see the module docstring for the dispatch table).

    Below one output tile the pad-to-128 overhead dominates any VMEM win."""
    return _resolve(
        "pairdist", _ENV_VAR, backend,
        lambda: not (n is not None and m is not None
                     and (n < TILE_I or m < TILE_J)))


def sqdist_xla(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """‖a_i − b_j‖² via the MXU-friendly ‖a‖²+‖b‖²−2ab form (pure XLA).

    The cross term is a full-f32 matmul: a TPU's default f32 matmul rounds
    its operands to bfloat16, and the cancellation in this form then
    leaves kernel matrices too far from PSD for a Cholesky."""
    aa = jnp.sum(a * a, axis=-1)
    bb = jnp.sum(b * b, axis=-1)
    cross = jnp.matmul(a, b.T, precision=jax.lax.Precision.HIGHEST)
    return jnp.maximum(aa[:, None] + bb[None, :] - 2.0 * cross, 0.0)


def rbf_xla(a: jnp.ndarray, b: jnp.ndarray, bandwidth: float) -> jnp.ndarray:
    d2 = sqdist_xla(a, b)
    return jnp.exp(-d2 / (2.0 * bandwidth * bandwidth + 1e-12))


@functools.partial(jax.jit, static_argnames=("bandwidth",))
def _pallas_padded(x: jnp.ndarray, y: jnp.ndarray,
                   bandwidth: float | None) -> jnp.ndarray:
    """Pad-and-slice wrapper: the ONLY place that knows the tile rules.

    Zero-padding the feature axis leaves distances unchanged; padded rows in
    N/M produce garbage distances that are sliced off before returning.
    """
    N, M = x.shape[0], y.shape[0]
    xp = pad_to(pad_to(x.astype(jnp.float32), LANE, axis=1), TILE_I, axis=0)
    yp = pad_to(pad_to(y.astype(jnp.float32), LANE, axis=1), TILE_J, axis=0)
    out = _raw_pairdist(xp, yp, bandwidth=bandwidth, interpret=use_interpret())
    return out[:N, :M]


def pairdist_auto(x: jnp.ndarray, y: jnp.ndarray, *,
                  bandwidth: float | None = None, backend: str = "auto",
                  differentiable: bool = False) -> jnp.ndarray:
    """Pairwise squared distance ``[N, M]`` (or fused RBF kernel when
    ``bandwidth`` is given) with automatic backend dispatch.

    ``differentiable=True`` pins the XLA path — pass it from any code that
    will be transformed by ``jax.grad`` (the Pallas kernel has no VJP).
    Shapes need no tile alignment on any path: the Pallas route pads to tile
    multiples and slices the result back.
    """
    if differentiable:
        be = "xla"
    else:
        be = resolve_backend(backend, x.shape[0], y.shape[0])
    if be == "xla":
        if bandwidth is None:
            return sqdist_xla(x, y)
        return rbf_xla(x, y, bandwidth)
    return _pallas_padded(x, y, None if bandwidth is None else float(bandwidth))


# ------------------------------------------------------------ pareto_count
def resolve_pareto_backend(backend: str = "auto",
                           n: int | None = None) -> str:
    """Resolve the dominance-count backend for an [n, m] problem — same
    dispatch table as :func:`resolve_backend` with its own env override
    (``REPRO_PARETO_BACKEND``): ``auto`` defaults to XLA everywhere (the
    fidelity default — bit-identical to the historical inline broadcast
    form), ``platform`` upgrades to the Pallas kernel on TPU for
    tile-worthy row counts."""

    def tile_ok():
        from .pareto_count.kernel import TILE_I as _PC_TILE

        return n is None or n >= _PC_TILE

    return _resolve("pareto", _PARETO_ENV_VAR, backend, tile_ok)


def dominance_counts_xla(y: jnp.ndarray) -> jnp.ndarray:
    """Strict-dominance counts [N] for minimization — the historical inline
    broadcast form (Definition 3 / Eq. (1) flipped to minimization)."""
    le = jnp.all(y[:, None, :] <= y[None, :, :], axis=-1)  # le[q,p]: q<=p
    lt = jnp.any(y[:, None, :] < y[None, :, :], axis=-1)
    return jnp.sum(jnp.logical_and(le, lt), axis=0)


def dominance_counts_auto(y: jnp.ndarray, *,
                          backend: str = "auto") -> jnp.ndarray:
    """Dominance counts with automatic backend dispatch — the
    ``pareto_count`` twin of :func:`pairdist_auto` (no tile-alignment
    requirements on any path; the Pallas route pads rows with ``+inf`` and
    slices back inside ``pareto_count.ops``)."""
    if resolve_pareto_backend(backend, y.shape[0]) == "xla":
        return dominance_counts_xla(y)
    from .pareto_count import ops as _ops

    return _ops.dominance_counts(y)


# ------------------------------------------------------------ systolic_eval
def resolve_systolic_backend(backend: str = "auto",
                             n: int | None = None) -> str:
    """Resolve the SoC cost-model backend for an [n, d] design batch — same
    dispatch table as :func:`resolve_backend` with its own env override
    (``REPRO_SYSTOLIC_BACKEND``): ``auto`` defaults to XLA everywhere (the
    fidelity default — the reference ``repro.soc.model.soc_metrics``),
    ``platform`` upgrades to the fused Pallas sweep kernel on TPU for
    tile-worthy batch sizes."""

    def tile_ok():
        from .systolic_eval.kernel import TILE_N as _SE_TILE

        return n is None or n >= _SE_TILE

    return _resolve("systolic", _SYSTOLIC_ENV_VAR, backend, tile_ok)


def soc_metrics_auto(vals: jnp.ndarray, layers: jnp.ndarray, *,
                     backend: str = "auto") -> jnp.ndarray:
    """Batched SoC metrics ``[N, 3]`` with automatic backend dispatch — the
    ``systolic_eval`` member of the family: every ``soc_metrics`` consumer
    (``VLSIFlow`` above all) routes here instead of choosing the reference
    model or the Pallas sweep kernel inline. No tile-alignment requirement
    on any path; the Pallas route pads the batch axis and slices back
    inside ``systolic_eval.ops``."""
    if resolve_systolic_backend(backend, vals.shape[0]) == "xla":
        from repro.soc.model import soc_metrics as _soc_metrics

        return _soc_metrics(vals, layers)
    from .systolic_eval import ops as _ops

    return _ops.soc_metrics(vals, layers)


# ------------------------------------------------------------- round_fused
def resolve_round_backend(backend: str = "auto",
                          n: int | None = None) -> str:
    """Resolve the fused acquisition-round backend for an n-candidate pool —
    same dispatch table as :func:`resolve_backend` with its own env override
    (``REPRO_ROUND_BACKEND``): ``auto`` defaults to XLA everywhere (the
    fidelity default — the engine's staged chunk-scanned round, whose HLO
    the golden trajectory fixtures pin byte-for-byte), ``platform`` upgrades
    to the fused Pallas round kernel on TPU for tile-worthy pools."""

    def tile_ok():
        from .round_fused.kernel import TILE_C as _RF_TILE

        return n is None or n >= _RF_TILE

    return _resolve("round", _ROUND_ENV_VAR, backend, tile_ok)


def round_score_auto(params_ref, L, V, x, beta, ystar, pool_c, evalm_c, base,
                     y_mean, y_std, weights, *, s0: int,
                     backend: str = "auto"):
    """One acquisition round's scoring half — trailing V-cache update,
    posterior moments, MES scoring, never-re-evaluate masking, global
    first-index-wins argmax — with automatic backend dispatch: the
    ``round_fused`` member of the family. Returns ``(V_new, best_idx)``.

    The XLA route IS the engine's staged math (``_v_chunk_refactor`` /
    ``_v_chunk_block`` scan + ``_select_chunks``), so ``auto``'s fidelity
    default is bit-identical to the engine rounds by construction; the
    Pallas route fuses all four stages into one launch per pool chunk
    (``round_fused.kernel``) and selects the identical candidate
    (pinned by ``tests/test_kernels.py``). ``s0`` rows of V are reused
    (``0`` = full refactor, ``>= P`` = score-only fantasy re-score);
    ``params_ref`` is the engine's ``GPParams`` factorization snapshot.
    """
    nc, C, _ = pool_c.shape
    P = L.shape[-1]
    if resolve_round_backend(backend, nc * C) == "xla":
        from repro.core.engine import (_select_chunks, _v_chunk_block,
                                       _v_chunk_refactor)

        if s0 >= P:
            V_new = V
        elif s0 <= 0:
            _, V_new = jax.lax.scan(
                lambda _, pc: (None, _v_chunk_refactor(params_ref, L, x, pc)),
                None, pool_c)
        else:
            _, V_new = jax.lax.scan(
                lambda _, inp: (None, _v_chunk_block(params_ref, L, inp[0],
                                                     x, inp[1], s0)),
                None, (V, pool_c))
        nxt = _select_chunks(params_ref, beta, ystar, V_new, y_mean, y_std,
                             evalm_c, base, weights)
        return V_new, nxt
    from .round_fused import ops as _ops

    return _ops.round_select(
        jnp.exp(params_ref.log_ls), jnp.exp(params_ref.log_var), L, V, x,
        beta, ystar, pool_c, evalm_c, y_mean, y_std, weights, s0=s0)


def auto_chunk(n: int, *, bytes_per_col: int = 4 * 3 * 256,
               budget_mb: int = DEFAULT_CHUNK_BUDGET_MB,
               floor: int = 2048) -> int:
    """Column-chunk size for streaming an O(n)-wide pool axis under a memory
    budget.

    ``bytes_per_col`` is the caller's per-candidate working set — the default
    models one column of the BO engine's V cache (``m = 3`` objectives ×
    ``P = 256`` padded training rows × f32). The result is clamped to
    ``[min(floor, n), n]`` so tiny pools stay single-chunk and huge pools
    never drop below a dispatch-amortizing block size.
    """
    if n < 1:
        raise ValueError(f"auto_chunk: n must be >= 1, got {n}")
    c = (budget_mb << 20) // max(bytes_per_col, 1)
    return int(min(n, max(floor, c)))


def pairdist_chunked(x: jnp.ndarray, y: jnp.ndarray, *, chunk: int,
                     bandwidth: float | None = None, backend: str = "auto",
                     differentiable: bool = False) -> jnp.ndarray:
    """:func:`pairdist_auto` assembled from ``[N, chunk]`` column blocks.

    Same values as the monolithic call — column blocks of the XLA form are
    bitwise-stable under chunking (pinned by ``tests/test_pool_scaling.py``)
    — but the pairwise temporaries are bounded by one block, so callers that
    need the full matrix of a very wide ``y`` (e.g. the TED kernel build on
    an uncapped pool) don't materialize intermediate [N, M] products all at
    once.
    """
    if chunk < 1:
        raise ValueError(f"pairdist_chunked: chunk must be >= 1, got {chunk}")
    m = y.shape[0]
    if chunk >= m:
        return pairdist_auto(x, y, bandwidth=bandwidth, backend=backend,
                             differentiable=differentiable)
    blocks = [pairdist_auto(x, y[j:j + chunk], bandwidth=bandwidth,
                            backend=backend, differentiable=differentiable)
              for j in range(0, m, chunk)]
    return jnp.concatenate(blocks, axis=1)
