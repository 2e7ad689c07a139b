"""Pallas TPU kernels: interpret mode off-TPU, compiled by Mosaic on a TPU.

The four main-path kernels compile for v5e (pinned by
``tests/test_tpu_compile.py``); ``flash_attn`` belongs to the LM stack.

- ``backend``       unified dispatch (XLA fidelity default; Pallas-on-TPU via
                    ``REPRO_PAIRDIST_BACKEND=platform`` or forced) with
                    pad-to-tile wrappers — every pairdist consumer routes here
- ``pairdist``      tiled ||xi-xj||^2 with fused RBF (TED + GP kernel matrices)
- ``pareto_count``  tiled Pareto dominance counting
- ``systolic_eval`` batched SoC cost-model evaluation (the "VLSI flow" on TPU)
- ``round_fused``   fused BO acquisition round: V-update → moments → MES →
                    masked argmax in one launch per pool chunk
- ``flash_attn``    causal flash attention (LM prefill hot loop)
"""
from . import common  # noqa: F401

__all__ = ["common", "backend", "pairdist", "pareto_count", "systolic_eval",
           "round_fused", "flash_attn"]
