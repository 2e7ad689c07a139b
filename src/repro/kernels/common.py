"""Shared kernel plumbing: interpret-mode fallback + padding helpers.

TPU is the *target*: every ``ops.py`` wrapper runs its kernel with
``interpret=True`` off-TPU (the kernel body executes with real BlockSpec
tiling semantics) and compiled by Mosaic on a TPU. ``pairdist``,
``pareto_count``, ``systolic_eval`` and ``round_fused`` compile for a v5e
chip at the main path's shapes; ``tests/test_tpu_compile.py`` pins that
with ahead-of-time compiles for a described v5e topology.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["use_interpret", "pad_to", "cdiv"]


def use_interpret() -> bool:
    return jax.default_backend() != "tpu"


def cdiv(a: int, b: int) -> int:
    return -(-a // b)


def pad_to(x: jnp.ndarray, multiple: int, axis: int,
           value: float = 0.0) -> jnp.ndarray:
    n = x.shape[axis]
    pad = (-n) % multiple
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)
