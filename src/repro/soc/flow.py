"""Flow runners: the callable ``idx -> metrics`` interface the tuner expects.

``VLSIFlow``      — the detailed SoC model (``model.py``), the paper's ground
                    truth stand-in. Counts its invocations (the tuner's budget
                    accounting and the benchmarks' "flow calls" both read it).
``SimplifiedFlow``— the SCALE-Sim-like single-kernel analytical model the
                    paper shows is misleading (Fig. 4(c)).
``DelayedFlow``   — wraps any flow with a fixed per-call sleep, the stand-in
                    for an hours-long real VLSI flow in the exploration
                    service's concurrency benchmarks and smoke tests.

All runners are **pool-safe**: picklable (device arrays are rebuilt on
unpickle, not shipped), so ``repro.service.FlowPool`` can dispatch them to
spawn-context worker processes. Those workers are pinned to the host CPU
backend, so a flow unpickled there evaluates on the CPU.
"""
from __future__ import annotations

import time

import numpy as np
import jax.numpy as jnp

from repro.core.space import DesignSpace
from .simplified import simplified_metrics
from .workloads import get_workload

__all__ = ["VLSIFlow", "SimplifiedFlow", "DelayedFlow"]


class VLSIFlow:
    def __init__(self, space: DesignSpace, workload: str | np.ndarray = "resnet50",
                 use_kernel: bool = False):
        self.space = space
        self.layers = (get_workload(workload) if isinstance(workload, str)
                       else np.asarray(workload))
        self._layers_j = jnp.asarray(self.layers, jnp.float32)
        self.calls = 0
        self.evaluated = 0
        self.use_kernel = use_kernel

    # Device buffers do not pickle (and must not: the worker process owns
    # its own jax runtime) — rebuild them from the host copy on unpickle.
    def __getstate__(self) -> dict:
        d = self.__dict__.copy()
        del d["_layers_j"]
        return d

    def __setstate__(self, d: dict) -> None:
        self.__dict__.update(d)
        self._layers_j = jnp.asarray(self.layers, jnp.float32)

    def __call__(self, idx: np.ndarray) -> np.ndarray:
        idx = np.atleast_2d(np.asarray(idx))
        self.calls += 1
        self.evaluated += idx.shape[0]
        vals = self.space.values(idx)
        # use_kernel=True pins the Pallas sweep kernel; otherwise dispatch
        # follows the shared backend table (env override, TPU platform
        # upgrade) like every other kernel family.
        from repro.kernels.backend import soc_metrics_auto

        return np.asarray(soc_metrics_auto(
            jnp.asarray(vals, jnp.float32), self._layers_j,
            backend="pallas" if self.use_kernel else "auto"))


class SimplifiedFlow(VLSIFlow):
    def __call__(self, idx: np.ndarray) -> np.ndarray:
        idx = np.atleast_2d(np.asarray(idx))
        self.calls += 1
        self.evaluated += idx.shape[0]
        vals = self.space.values(idx)
        return np.asarray(simplified_metrics(jnp.asarray(vals, jnp.float32),
                                             self._layers_j))


class DelayedFlow:
    """Any flow + a fixed per-call sleep — a mock of the real VLSI flow's
    hours-per-point latency. One *call* sleeps once however many rows it
    evaluates, mirroring a batch submitted to a farm in parallel; the
    service's per-candidate dispatch therefore pays one delay per candidate
    while q concurrent workers overlap theirs — exactly the regime the
    q-batch speedup benchmark measures (``benchmarks/service_bench.py``)."""

    def __init__(self, flow, delay_s: float):
        self.flow = flow
        self.delay_s = float(delay_s)

    def __call__(self, idx: np.ndarray) -> np.ndarray:
        time.sleep(self.delay_s)
        return self.flow(idx)
