"""Fleet-wide telemetry: metrics registry, event log, spans, trace reports.

The exploration stack's value claim is *efficiency* — evaluations-to-ADRS
under a VLSI-flow budget — so its runtime behavior (queue depths, flow
latencies, cache hit rates, scheduler cycle walls, per-round engine stage
breakdowns) must be first-class observable. This package is that layer,
with one hard invariant: **zero perturbation**. Everything here is
host-side Python — plain dicts, floats and file appends, never anything
inside traced/jitted code — so every golden trajectory stays byte-identical
with telemetry fully enabled (proven by ``tests/test_obs.py``).

- ``metrics``  :class:`MetricsRegistry` — named counters, gauges and
               histograms with optional labels; ``snapshot()`` returns one
               JSON-able dict (the wire ``metrics`` verb's payload) and
               :func:`render_prometheus` turns a snapshot into Prometheus
               text exposition format.
- ``events``   :class:`EventLog` — an append-only JSON-lines log of span
               begin/end and instant events with monotonic timestamps and
               a run-generation field; atomic line writes, and a crash +
               resume *appends a new generation* instead of corrupting or
               double-counting (generation bookkeeping survives SIGKILL).
- ``progress`` :func:`log_progress` — the ONE per-round progress helper
               shared by ``soc_tuner`` / ``fleet_tuner`` / the service
               runners / server jobs: builds the history record, prints
               the verbose line, and emits the matching event-log record.
- ``spans``    :func:`span` / :func:`records` — the program's layer
               boundaries (``tuner.round``, ``engine.select``,
               ``engine.fetch``, ...) on the JAX profiler's clock: active
               only while a profiler trace is, then written into the trace
               beside the device ops and kept in memory for the
               benchmark's readers; a single ``is_enabled`` check otherwise.
- ``trace``    :func:`build_chrome_trace` / :func:`summarize_events` —
               render an event log into a Chrome ``trace_event`` JSON
               (loadable in ``chrome://tracing`` / Perfetto) and a per-track
               timeline summary (the ``tools/trace_report.py`` backend).

See ``docs/observability.md`` for the registry model, the event schema,
the wire verb and worked Prometheus / Chrome-trace examples.
"""
from .events import EventLog, read_events
from .metrics import (Counter, Gauge, Histogram, MetricsRegistry,
                      render_prometheus)
from .progress import log_progress
from .spans import SpanRecord, records, span
from .trace import build_chrome_trace, summarize_events

__all__ = [
    "MetricsRegistry", "Counter", "Gauge", "Histogram", "render_prometheus",
    "EventLog", "read_events",
    "log_progress",
    "span", "records", "SpanRecord",
    "build_chrome_trace", "summarize_events",
]
