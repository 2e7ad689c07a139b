"""Traffic: the generator a traffic file names drives the program's user
entry, and the flow tap records what the timed path produced and times its
rounds.

A traffic file ``bench/traffic/<mix>.json`` holds the mix's parameters and
names its generator, ``bench/generators/<generator>.py``. A generator
module defines

* ``build_context(cell, seed)`` — the cell's data from the seed;
* ``run_explorations(ctx, closed, *, spans=False, out=None)`` — drives the
  entry until ``closed()`` and returns the :class:`Exploration` records
  (appended to ``out`` as they start, where given);
* ``key_rounds(ctx, exp)`` and ``replay_exploration(ctx, exp, check,
  ariths, record)`` — the engine's part of the check (``verdict.py``).

A new mix whose generator exists is a data file; a mix that needs another
way of driving the program adds a generator module beside it. Neither edits
a file that is there.

Rounds are counted from the flow the harness hands to the program
(:class:`FlowTap`): an exploration's first ``prologue`` flow calls are its
prologue; every later call evaluates one round's pick and ends that round.
A round runs from the end of the previous flow call to the end of its own.
The tap also closes the window: a flow call after the deadline raises
:class:`WindowClosed`, which the generator catches.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import os
import sys
import time

import numpy as np

GENERATORS_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "generators")


class WindowClosed(Exception):
    """Raised by the flow tap at the deadline; ends the exploration."""


@dataclasses.dataclass
class Call:
    designs: np.ndarray     # [k, 26] design index vectors as evaluated
    y: np.ndarray           # [k, 3] metrics the flow returned
    t0: float
    t1: float


@dataclasses.dataclass
class Round:
    exploration: int
    index: int           # 0-based round of its exploration
    t_start: float
    t_end: float
    flow_s: float
    call: int = -1       # the pick's flow call, an index into ``calls``

    @property
    def seconds(self) -> float:
        return self.t_end - self.t_start


@dataclasses.dataclass
class Exploration:
    index: int
    key: object
    t_start: float
    workload: str = ""   # the workload the flow evaluates
    prologue: int = 0    # flow calls before the first round
    calls: list = dataclasses.field(default_factory=list)
    rounds: list = dataclasses.field(default_factory=list)  # Round
    result: object = None      # what the entry returned, when it ran out
    final_adrs: float | None = None
    error: str | None = None   # the exception, when a round raised


class Span:
    """A profiler annotation opened and closed at different places of one
    thread (the harness's spans: exploration, prologue, round, flow)."""

    def __init__(self, name: str, on: bool):
        self._a = None
        if on:
            import jax

            self._a = jax.profiler.TraceAnnotation(name)
            self._a.__enter__()

    def close(self) -> None:
        if self._a is not None:
            self._a.__exit__(None, None, None)
            self._a = None


class FlowTap:
    """The flow handed to the program: evaluates with the user's flow and
    records every call and round of the current exploration."""

    def __init__(self, flow, closed, prologue: int, spans: bool = False):
        self.flow = flow
        self.closed = closed          # () -> bool: the window's deadline
        self.prologue = prologue
        self.spans = spans
        self.exp: Exploration | None = None
        self._phase: Span | None = None

    def begin(self, exp: Exploration) -> None:
        self.exp = exp
        self._phase = Span("prologue", self.spans)

    def end(self) -> None:
        if self._phase is not None:
            self._phase.close()
            self._phase = None

    def __call__(self, idx):
        if self.closed():
            raise WindowClosed()
        exp = self.exp
        idx = np.atleast_2d(np.asarray(idx))
        flow_span = Span("flow", self.spans)
        t0 = time.monotonic()
        y = np.asarray(self.flow(idx))
        t1 = time.monotonic()
        flow_span.close()
        prev_end = exp.calls[-1].t1 if exp.calls else exp.t_start
        exp.calls.append(Call(idx.copy(), y.copy(), t0, t1))
        k = len(exp.calls)
        if k > self.prologue:
            exp.rounds.append(Round(exp.index, k - self.prologue - 1,
                                    prev_end, t1, t1 - t0, k - 1))
        if k >= self.prologue:
            self._phase.close()
            self._phase = Span("round", self.spans)
        return y


def exploration_key(seed: int, i: int):
    import jax

    return jax.random.fold_in(jax.random.PRNGKey(seed % 2**32), i)


_loaded: dict = {}


def generator(name: str, generators_dir: str = GENERATORS_DIR):
    """The module ``<generators_dir>/<name>.py``."""
    path = os.path.join(generators_dir, name + ".py")
    if path not in _loaded:
        spec = importlib.util.spec_from_file_location(
            "bench_generator_" + name.replace("-", "_").replace(".", "_"),
            path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[spec.name] = mod
        spec.loader.exec_module(mod)
        _loaded[path] = mod
    return _loaded[path]
