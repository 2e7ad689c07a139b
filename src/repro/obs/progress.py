"""The ONE per-round progress helper shared by every exploration driver.

``soc_tuner``, the service runner, the fleet runner and server jobs used to
carry three near-identical copies of "build the history record, print the
verbose line" — :func:`log_progress` is the single implementation, and it
additionally emits the matching event-log record so the on-disk timeline
and the in-memory history can never disagree.

The history record itself still comes from
:func:`repro.core.tuner.round_record` (the schema the figure scripts and
``engine_bench`` read) — this helper adds NOTHING to it, so histories stay
byte-identical with telemetry on or off. Under a profiler trace the helper
is the span ``tuner.record`` (:mod:`repro.obs.spans`).
"""
from __future__ import annotations

from .spans import span

__all__ = ["log_progress"]


def log_progress(history: list, y, n_evaluated: int, i: int,
                 reference_front=None, *, verbose: bool = False,
                 tag: str = "tuner", label: str | None = None,
                 word: str = "round", wall_s: float | None = None,
                 events=None, track: str | None = None,
                 **event_fields) -> dict:
    """Append round ``i``'s record to ``history``; optionally print the
    progress line and emit the event-log instant.

    ``tag``/``label``/``word`` reproduce each driver's historical verbose
    format exactly (``[service] eval   7 ...`` vs
    ``[fleet-svc] resnet50:s0   round   7 ...``). ``events`` is an
    :class:`repro.obs.events.EventLog` or None; ``track`` defaults to the
    label so per-scenario/per-job rows separate in the Chrome trace.
    Extra keyword fields ride along on the event record only.
    """
    from repro.core.tuner import round_record  # deferred: core imports obs

    with span("tuner.record"):
        rec = round_record(y, n_evaluated, i, reference_front, wall_s=wall_s)
        history.append(rec)
        if verbose:
            head = f"[{tag}] "
            if label is not None:
                head += f"{label:<24s} "
            num = f"{i:4d}" if word == "eval" else f"{i:3d}"
            print(head + f"{word} {num} evals={rec['evaluations']:4d} "
                  f"front={rec['pareto_size']:3d}"
                  + (f" adrs={rec['adrs']:.4f}" if "adrs" in rec else ""))
        if events is not None:
            events.instant(
                "round", cat="progress",
                track=track if track is not None else (label or tag),
                round=i, evaluations=rec["evaluations"],
                pareto_size=rec["pareto_size"],
                **({"adrs": rec["adrs"]} if "adrs" in rec else {}),
                **({"wall_s": wall_s} if wall_s is not None else {}),
                **event_fields)
        return rec
