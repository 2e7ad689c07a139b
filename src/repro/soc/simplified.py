"""The "simplified analytical model" baseline ([6], SCALE-Sim-like).

Reports per-layer systolic cycles with perfect utilization and no memory /
host / control modeling — exactly the class of tool the paper shows produces
misleading Pareto fronts (Fig. 4(c)). Kept deliberately naive.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .model import CONST, _ceil_div, decode_design

__all__ = ["simplified_metrics"]


@jax.jit
def simplified_metrics(vals: jnp.ndarray, layers: jnp.ndarray) -> jnp.ndarray:
    vals = jnp.asarray(vals, jnp.float32)
    layers = jnp.asarray(layers, jnp.float32)
    d = decode_design(vals)
    M, K, N, reps, _ = (layers[:, i] for i in range(5))
    R, C = d["R"][:, None], d["C"][:, None]
    # SCALE-Sim's WS estimate: (2R + C + K - 2) per (M/R x N/C) fold, ideal.
    folds = _ceil_div(M[None], R) * _ceil_div(N[None], C)
    cycles = jnp.sum(folds * (2.0 * R + C + K[None] - 2.0) * reps[None], axis=1)
    latency_ms = cycles / CONST["freq_hz"] * 1e3
    macs = jnp.sum(M * K * N * reps)
    e_mac = CONST["e_mac8"] * d["ib"] ** 1.7
    power_mw = (macs * e_mac * 1e-12) / (cycles / CONST["freq_hz"]) * 1e3
    pe = CONST["a_pe8"] * d["ib"] ** 1.25
    mb = 1.0 / (1024.0 * 1024.0)
    area = d["R"] * d["C"] * pe + d["spad_bytes"] * mb * CONST["a_sram_mb"] \
        + d["acc_bytes"] * mb * CONST["a_acc_sram_mb"]
    return jnp.stack([latency_ms, power_mw, area], axis=1)
