"""driver_ms.single: the program's ``tuner.round`` spans less their
``engine.select``, ``engine.observe`` and ``tuner.flow`` children (the key
splits, the frontier subset draw and the history record), per round of the
window. Rounds cut by the deadline are left out."""
from benchlib import program_spans as ps


def read(run):
    spans = ps.window_spans(run)
    if spans is None:
        return None
    return ps.per_round_ms(run, ps.seconds(spans, "tuner.round")
                           - ps.child_seconds(spans, "tuner.round",
                                              ("engine.select",
                                               "engine.observe",
                                               "tuner.flow")))
