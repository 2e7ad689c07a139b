"""Pallas TPU kernel: batched SoC cost-model evaluation.

The paper's bottleneck is its evaluator (days of VLSI flow per design); ours
is an analytical SoC model cheap enough to batch — so the TPU-native move is
to make *the evaluator itself* an accelerator kernel: one grid step evaluates
a 128-design tile against the whole workload, with every (design x layer)
intermediate resident in VMEM. The body **reuses the exact jnp math** from
``repro.soc.model`` (decode_design / _layer_cost / epilogue), so the Pallas
kernel and the oracle cannot drift apart: the kernel is the same program,
re-tiled.

At 2500 designs x 58 layers the jnp version streams ~60 [N, L] f32
intermediates through HBM; the kernel touches HBM once for vals [N, 26] and
once for metrics [N, 3].
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

TILE_N = 128


def _body(vals_ref, lrows_ref, out_ref):
    from repro.soc.model import _metrics_cols

    vals = vals_ref[...].astype(jnp.float32)     # [TN, 26]
    lrows = lrows_ref[...].astype(jnp.float32)   # [5, L]
    # each layer column as a [1, L] row slice: Mosaic cannot relayout a
    # lane-strided column of the [L, 5] table into a row
    out_ref[...] = _metrics_cols(vals, tuple(lrows[i:i + 1] for i in range(5)))


def soc_metrics(vals: jnp.ndarray, layers: jnp.ndarray, *,
                interpret: bool = False) -> jnp.ndarray:
    """vals [N, 26] (N a tile multiple), layers [L, 5] -> [N, 3]."""
    N, F = vals.shape
    L = layers.shape[0]
    grid = (N // TILE_N,)
    return pl.pallas_call(
        _body,
        grid=grid,
        in_specs=[
            pl.BlockSpec((TILE_N, F), lambda i: (i, 0)),
            pl.BlockSpec((5, L), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((TILE_N, 3), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((N, 3), jnp.float32),
        interpret=interpret,
    )(vals, layers.T)
