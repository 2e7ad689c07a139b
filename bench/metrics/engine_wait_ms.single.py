"""engine_wait_ms.single: the program's ``engine.fetch`` spans, the host
blocked on a round's device result, per round of the window. Spans cut by
the deadline are left out."""
from benchlib import program_spans as ps


def read(run):
    spans = ps.window_spans(run)
    if spans is None:
        return None
    return ps.per_round_ms(run, ps.seconds(spans, "engine.fetch"))
