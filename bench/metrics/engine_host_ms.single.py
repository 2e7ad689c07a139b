"""engine_host_ms.single: the program's ``engine.select`` spans less their
``engine.fetch`` children, plus its ``engine.observe`` spans: the engine's
host work to build and launch a round, per round of the window. Spans cut
by the deadline are left out."""
from benchlib import program_spans as ps


def read(run):
    spans = ps.window_spans(run)
    if spans is None:
        return None
    return ps.per_round_ms(run, ps.seconds(spans, "engine.select")
                           - ps.child_seconds(spans, "engine.select",
                                              ("engine.fetch",))
                           + ps.seconds(spans, "engine.observe"))
