"""The comparison that decides ``correct``.

After the window has closed, what it produced is compared with the plain
reference (``reference.py``), which imports nothing of the program:

* **flow** — ``flow_rel_err``: the largest relative gap, over every metric
  of every design the flow evaluated in the window, between the program's
  metrics and the reference cost model's (float64).
* **engine** — ``pick_rank``: at the checked rounds the generator's replay
  rebuilds each round from the designs the timed path sent to the flow (for
  ``soc_tuner``: ICD importance and transform, the GP fit on the training
  set the driver had then under the engine's documented fit schedule, the
  posterior over the whole pool, the frontier draw from the round's key and
  the MES acquisition of every unevaluated candidate). A round's rank is the
  share of unevaluated candidates that the reference scores above the
  program's pick; ``pick_rank`` is the mean over the checked rounds, so a
  fault that moves a few rounds' picks far down shows as much as one that
  moves many a little. The checked rounds are the window's last round,
  every key round (``key_rounds``: for ``soc_tuner`` the first round of
  each 8-row bucket, where the incremental engine refactors) of one
  exploration drawn from the seed, and ``check.rounds`` more drawn from the
  seed. The largest rank (``pick_rank_max``) and the pick's acquisition
  gap, (best - pick) / (best - median), go to the diagnostic line.

Counted as failed: a round that raised, a round whose metrics are not
finite, a pick the exploration had already evaluated or that is not a pool
design, and an exploration the reference cannot replay (its ICD trials are
not the rows its key draws).
"""
from __future__ import annotations

import dataclasses
import math

import numpy as np

from . import reference as R


@dataclasses.dataclass
class Readings:
    attempted: int = 0
    failed: int = 0
    reasons: list = dataclasses.field(default_factory=list)
    flow_rel_err: dict = dataclasses.field(default_factory=dict)  # arith -> x
    pick_gap: dict = dataclasses.field(default_factory=dict)      # arith -> x
    ranks: dict = dataclasses.field(default_factory=dict)  # arith -> [x]
    checked_rounds: int = 0
    worst: dict = dataclasses.field(default_factory=dict)  # the program's

    @property
    def pick_rank(self) -> dict:
        return {k: float(np.mean(v)) for k, v in self.ranks.items()}

    @property
    def pick_rank_max(self) -> dict:
        return {k: max(v) for k, v in self.ranks.items()}


def _fail(rd: Readings, why: str) -> None:
    rd.failed += 1
    if len(rd.reasons) < 8:
        rd.reasons.append(why)


def _gap(a_ref: np.ndarray, pick: int) -> tuple[float, float]:
    """(gap, rank) of ``pick`` under the reference acquisition: the gap
    below the best in units of (best - median), and the share of unevaluated
    candidates that the reference scores above it."""
    live = a_ref[np.isfinite(a_ref)]
    best = float(live.max())
    spread = max(best - float(np.median(live)), 1e-12)
    if not np.isfinite(a_ref[pick]):
        return float("inf"), 1.0
    above = int(np.count_nonzero(live > a_ref[pick]))
    return (best - float(a_ref[pick])) / spread, above / live.size


def check_sample(ctx, gen, explorations: list, n_check: int,
                 rng: np.random.Generator) -> dict:
    """{exploration index: set of round indices} to check."""
    rounds = [(exp.index, r.index) for exp in explorations
              for r in exp.rounds]
    if not rounds:
        return {}
    sample = {rounds[-1]}
    ran = [e for e in explorations if e.rounds]
    full = [e for e in ran if e.result is not None] or [
        max(ran, key=lambda e: len(e.rounds))]
    exp = full[int(rng.integers(len(full)))]
    sample |= {(exp.index, r) for r in gen.key_rounds(ctx, exp)}
    rest = [x for x in rounds if x not in sample]
    k = min(n_check, len(rest))
    sample |= {rest[i] for i in rng.choice(len(rest), size=k, replace=False)}
    by_exp: dict = {}
    for e, r in sample:
        by_exp.setdefault(e, set()).add(r)
    return by_exp


def judge(ctx, gen, explorations: list, *, n_check: int,
          rng: np.random.Generator, gp_controls=(), flow_controls=(),
          at: dict | None = None) -> Readings:
    """Readings of the program (key ``program``) and, where asked, of the
    controls (keyed by their arithmetic's name) against the reference.
    ``at`` ({exploration: rounds}) replaces the drawn sample."""
    rd = Readings()
    for exp in explorations:
        seen = set()
        for c in exp.calls[:exp.prologue]:
            seen.update(d.tobytes() for d in c.designs)
        for rnd in exp.rounds:
            c = exp.calls[rnd.call]
            rd.attempted += 1
            if not np.all(np.isfinite(c.y)):
                _fail(rd, f"exploration {exp.index} round {rnd.index}: "
                          "non-finite metrics")
            for b in (d.tobytes() for d in c.designs):
                if b in seen:
                    _fail(rd, f"exploration {exp.index} round {rnd.index}: "
                              "design evaluated twice")
                if b not in ctx.row_of:
                    _fail(rd, f"exploration {exp.index} round {rnd.index}: "
                              "pick is not a pool design")
                seen.add(b)
        if exp.error is not None:
            rd.attempted += 1
            _fail(rd, f"exploration {exp.index}: {exp.error}")

    # flow: every design evaluated in the window
    by_workload: dict = {}
    for exp in explorations:
        by_workload.setdefault(exp.workload, []).extend(exp.calls)
    for workload, calls in by_workload.items():
        if not calls:
            continue
        layers = R.workload(workload)
        designs = np.concatenate([c.designs for c in calls], 0)
        y_prog = np.concatenate([c.y for c in calls], 0).astype(np.float64)
        y_ref = R.soc_metrics(designs, layers)
        for name, y_cmp in [("program", y_prog)] + [
                (ar.name, R.soc_metrics(designs, layers, ar))
                for ar in flow_controls]:
            err = np.abs(y_cmp - y_ref) / np.abs(y_ref)
            err = float(np.nan_to_num(err, nan=np.inf).max())
            rd.flow_rel_err[name] = max(rd.flow_rel_err.get(name, 0.0), err)

    # engine: the checked rounds
    if rd.failed:
        return rd
    sample = at if at is not None else check_sample(ctx, gen, explorations,
                                                    n_check, rng)
    for exp in explorations:
        if exp.index not in sample:
            continue

        def record(r, rows, a_ref, choices, exp=exp):
            for name, choice in choices:
                g, rank = _gap(a_ref, choice)
                if name == "program" and g > rd.pick_gap.get(name, -1.0):
                    top = np.sort(a_ref[np.isfinite(a_ref)])[::-1]
                    rd.worst = {"exploration": exp.index, "round": r,
                                "rows": rows, "gap": g, "rank": rank,
                                "ref_top5": top[:5].tolist(),
                                "ref_median": float(np.median(top)),
                                "ref_at_pick": float(a_ref[choice])}
                rd.pick_gap[name] = max(rd.pick_gap.get(name, 0.0), g)
                rd.ranks.setdefault(name, []).append(rank)
            rd.checked_rounds += 1

        why = gen.replay_exploration(ctx, exp, sample[exp.index],
                                     [R.F64, *gp_controls], record)
        if why is not None:
            _fail(rd, why)
    return rd


def decide(rd: Readings, limits: dict) -> tuple[bool, dict]:
    """``correct`` and each number compared beside its limit."""
    checks = {}
    ok = rd.failed == 0 and rd.attempted > 0 and rd.checked_rounds > 0
    for name, readings in (("flow_rel_err", rd.flow_rel_err),
                           ("pick_rank", rd.pick_rank)):
        value = readings.get("program", math.inf)
        value = value if math.isfinite(value) else 1e308
        checks[name] = {"value": value, "limit": limits[name]}
        ok = ok and value <= limits[name]
    return bool(ok), checks
