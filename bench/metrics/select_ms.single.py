"""select_ms.single: host clock, the mean over the window's rounds of the
round's duration less its flow call: the driver's and the engine's part of a
round (key splits, the frontier subset draw, the engine's select, observe
and the round's ADRS record)."""


def read(run):
    if not run.rounds:
        return None
    return 1e3 * sum(r.seconds - r.flow_s for r in run.rounds) / len(run.rounds)
