"""The program's own spans of a traced window, for the metrics that split
a round's host time by layer.

The program marks its layer boundaries with ``repro.obs.span``
(``tuner.round``, ``engine.select``, ``engine.fetch``, ...). While a
profiler trace is active, and only then, each span is also kept in memory
with its parent and its ``time.monotonic()`` stamps, the clock of the
harness's rounds. Only the window is traced, so what
``repro.obs.records()`` holds after it is the window's spans.
"""
from __future__ import annotations


def window_spans(run) -> list | None:
    """The window's spans that closed normally, less every span inside one
    that an exception closed (the window's deadline cuts the last round);
    None where the program has no recorder or recorded nothing."""
    try:
        from repro.obs import records
    except ImportError:
        return None
    spans = [r for r in records() if r.t1 is not None]
    if run.explorations:
        start = min(e.t_start for e in run.explorations)
        spans = [r for r in spans if r.t0 >= start]
    cut: set = set()
    for r in sorted(spans, key=lambda r: r.id):
        if r.failed or r.parent in cut:
            cut.add(r.id)
    spans = [r for r in spans if r.id not in cut]
    return spans or None


def seconds(spans: list, name: str) -> float:
    return sum(r.t1 - r.t0 for r in spans if r.name == name)


def child_seconds(spans: list, parent: str, names: tuple) -> float:
    """Time of the spans named ``names`` directly inside spans ``parent``."""
    ids = {r.id for r in spans if r.name == parent}
    return sum(r.t1 - r.t0 for r in spans
               if r.parent in ids and r.name in names)


def per_round_ms(run, total_s: float) -> float | None:
    if not run.rounds:
        return None
    return 1e3 * total_s / len(run.rounds)
