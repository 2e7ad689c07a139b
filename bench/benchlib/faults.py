"""Faults and the control, planted under the timed path, to show that the
check catches them.

Each is a context manager that patches the program in this process only,
for the fault tests and for ``control.py --faults``; the benchmark's own
runs never use them.

* ``state_unchanged`` — the engine keeps its first observations and ignores
  every later one, as a step that returns its state unchanged;
* ``half_batch`` — the engine keeps every other row of each observation,
  so the GP is fitted on half the training set;
* ``flow_altered`` — the flow's latency comes back 0.1% high;
* ``pick_altered`` — each round's pick is replaced by the next pool row
  that the exploration has not evaluated;
* ``refactor_altered`` — the same, on refactor rounds only: where the
  incremental engine refactors, or where the exact engine's training set
  enters a new 8-row bucket (a fault of a path that runs on some rounds);
* ``flow_bf16`` — the control: the reference cost model computed with
  bfloat16 intermediates, put in the flow's place.
"""
from __future__ import annotations

import contextlib

import numpy as np

FAULTS = ("state_unchanged", "half_batch", "flow_altered", "pick_altered",
          "refactor_altered", "flow_bf16")


@contextlib.contextmanager
def _patch(obj, name, value):
    old = getattr(obj, name)
    setattr(obj, name, value)
    try:
        yield
    finally:
        setattr(obj, name, old)


@contextlib.contextmanager
def planted(fault: str | None):
    if fault is None:
        yield
        return
    from repro.core.engine import BOEngine
    from repro.soc.flow import VLSIFlow

    if fault == "state_unchanged":
        orig = BOEngine.observe

        def observe(self, rows, y):
            if not self._rows:
                orig(self, rows, y)

        with _patch(BOEngine, "observe", observe):
            yield
    elif fault == "half_batch":
        orig = BOEngine.observe

        def observe(self, rows, y):
            rows = np.asarray(rows).reshape(-1)
            keep = slice(None) if len(rows) == 1 else slice(None, None, 2)
            orig(self, rows[keep], np.atleast_2d(np.asarray(y))[keep])

        with _patch(BOEngine, "observe", observe):
            yield
    elif fault == "flow_altered":
        orig = VLSIFlow.__call__

        def call(self, idx):
            y = np.array(orig(self, idx))
            y[:, 0] *= 1.001
            return y

        with _patch(VLSIFlow, "__call__", call):
            yield
    elif fault in ("pick_altered", "refactor_altered"):
        orig = BOEngine.select

        def select(self, key, sub_rows=None):
            refactors = self.stats.refactors
            bucket = -(-len(self._rows) // self.bucket)
            new_bucket = bucket != getattr(self, "_fault_bucket", None)
            self._fault_bucket = bucket
            pick = int(orig(self, key, sub_rows))
            if fault == "refactor_altered" and not (
                    self.stats.refactors > refactors if self.incremental
                    else new_bucket):
                return pick
            seen = set(self._rows)
            nxt = (pick + 1) % self.N
            while nxt in seen:
                nxt = (nxt + 1) % self.N
            return nxt

        with _patch(BOEngine, "select", select):
            yield
    elif fault == "flow_bf16":
        from . import reference as R

        def call(self, idx):
            layers = next(R.workload(w) for w in R.WORKLOADS
                          if np.array_equal(R.workload(w), self.layers))
            return R.soc_metrics(np.atleast_2d(np.asarray(idx)), layers,
                                 R.Arith("bf16"))

        with _patch(VLSIFlow, "__call__", call):
            yield
    else:
        raise ValueError(f"unknown fault {fault!r}; faults: {FAULTS}")
