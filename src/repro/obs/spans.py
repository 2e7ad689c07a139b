"""Spans on the JAX profiler's clock: the program's own layer boundaries.

:func:`span` marks a stretch of host code (a BO round, the engine's select,
the wait for a device result) with a name and a few attributes::

    with span("engine.select", path="incremental", P=96) as sp:
        ...
        sp.set(refactor=did_ref)

**The profiler being active is the switch.** Outside a
``jax.profiler.trace`` a span costs one ``TraceMe.is_enabled()`` check
(~0.2 µs on a CPU host): no attribute is formatted, nothing is stored and
nothing waits for the device. Inside a trace a span

- opens a :class:`jax.profiler.TraceAnnotation`, so it lands on the host
  plane of the same ``.xplane.pb`` as the device ops and shares their clock
  (an idle gap of the device can be put down to the span open around it);
- appends one :class:`SpanRecord` to a process-wide list, read back with
  :func:`records`: the name, the enclosing span of the same thread, the
  ``time.monotonic()`` stamps of its start and end, the attributes, and
  whether an exception closed it.

The list is process-wide because the profiler is: one trace covers every
thread of the process, and a reader of the trace's spans sees them all.
It grows only while a trace is active.

Attributes must already be host values (ints, floats, bools, strings): a
device scalar passed as an attribute would make the host wait for the
device.

Spans are the profiler-clock half of :mod:`repro.obs`; the
:class:`~repro.obs.events.EventLog` is the always-on, on-disk half. See
``docs/observability.md`` for which to use for what.
"""
from __future__ import annotations

import dataclasses
import itertools
import threading
import time

import jax
from jax._src.lib import _profiler

__all__ = ["SpanRecord", "span", "records"]

_tracing = _profiler.TraceMe.is_enabled


@dataclasses.dataclass
class SpanRecord:
    """One span of a traced stretch of the program."""

    name: str
    id: int                  # process-wide, in order of opening
    parent: int | None       # id of the enclosing span of the same thread
    t0: float                # time.monotonic() at the start
    t1: float | None = None  # ... at the end; None while open
    attrs: dict = dataclasses.field(default_factory=dict)
    failed: bool = False     # closed by an exception

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0


_records: list[SpanRecord] = []
_ids = itertools.count()
_local = threading.local()


def records() -> list[SpanRecord]:
    """Every span recorded in this process so far, in order of opening (a
    new list; the records themselves are shared, and an open span's ``t1``
    is None)."""
    return list(_records)


class _Span:
    __slots__ = ("_rec", "_ann")

    def __init__(self, name: str, attrs: dict):
        self._rec = SpanRecord(name, -1, None, 0.0, attrs=attrs)
        self._ann = jax.profiler.TraceAnnotation(name, **attrs)

    def __enter__(self) -> "_Span":
        stack = getattr(_local, "stack", None)
        if stack is None:
            stack = _local.stack = []
        rec = self._rec
        rec.id = next(_ids)
        rec.parent = stack[-1].id if stack else None
        self._ann.__enter__()
        rec.t0 = time.monotonic()
        stack.append(rec)
        _records.append(rec)
        return self

    def set(self, **attrs) -> None:
        """Add attributes known only once the span has run a while."""
        self._rec.attrs.update(attrs)
        self._ann.set_metadata(**attrs)

    def __exit__(self, exc_type, exc, tb) -> bool:
        rec = self._rec
        rec.t1 = time.monotonic()
        rec.failed = exc_type is not None
        _local.stack.pop()
        self._ann.__exit__(exc_type, exc, tb)
        return False


class _NoSpan:
    """What :func:`span` returns outside a trace: does nothing."""

    __slots__ = ()

    def __enter__(self) -> "_NoSpan":
        return self

    def set(self, **attrs) -> None:
        pass

    def __exit__(self, exc_type, exc, tb) -> bool:
        return False


_NO_SPAN = _NoSpan()


def span(name: str, **attrs):
    """A context manager that marks its body as the span ``name`` while a
    profiler trace is active, and does nothing otherwise (module
    docstring). ``set(**attrs)`` on the entered span adds attributes."""
    if not _tracing():
        return _NO_SPAN
    return _Span(name, attrs)
