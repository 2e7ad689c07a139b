"""The readers of the program's spans: self-time subtraction, spans cut by
the deadline left out, and nothing read where the program records no spans
(a commit without the recorder, or an untraced run)."""
import types

import pytest

import repro.obs
from benchlib import cells, drive

READERS = ("driver_ms.single", "engine_host_ms.single",
           "engine_wait_ms.single")


def _rec(id_, name, t0, t1, parent=None, failed=False):
    return types.SimpleNamespace(id=id_, name=name, parent=parent, t0=t0,
                                 t1=t1, attrs={}, failed=failed)


def _run(n_rounds):
    rounds = [drive.Round(0, i, 0.0, 1.0, 0.1) for i in range(n_rounds)]
    exp = drive.Exploration(0, None, 0.0, "resnet50", 2)
    return types.SimpleNamespace(rounds=rounds, explorations=[exp],
                                 trace=None)


def _round(base, t0, *, cut=False):
    """One ``tuner.round`` of 100 ms: keys 5, select 60 (fetch 20 inside),
    flow 10, observe 5, record 10; ``cut`` raises in the flow call."""
    r = [_rec(base, "tuner.round", t0, t0 + 0.100, failed=cut),
         _rec(base + 1, "tuner.keys", t0, t0 + 0.005, base),
         _rec(base + 2, "engine.select", t0 + 0.005, t0 + 0.065, base),
         _rec(base + 3, "engine.pack", t0 + 0.005, t0 + 0.035, base + 2),
         _rec(base + 4, "engine.fetch", t0 + 0.045, t0 + 0.065, base + 2),
         _rec(base + 5, "tuner.flow", t0 + 0.065, t0 + 0.075, base,
              failed=cut)]
    if not cut:
        r += [_rec(base + 6, "engine.observe", t0 + 0.075, t0 + 0.080, base),
              _rec(base + 7, "tuner.record", t0 + 0.080, t0 + 0.090, base)]
    return r


def _read(monkeypatch, recs, run):
    monkeypatch.setattr(repro.obs, "records", lambda: list(recs),
                        raising=False)
    return {m: cells.metric_reader(m)(run) for m in READERS}


def test_readers_split_the_round_by_self_time(monkeypatch):
    recs = (_round(0, 1.0) + _round(10, 1.1)
            + [_rec(20, "engine.observe", 0.9, 0.904)])   # before round 0
    got = _read(monkeypatch, recs, _run(2))
    # driver: 100 - 60 select - 10 flow - 5 observe = 25 per round
    assert got["driver_ms.single"] == pytest.approx(25.0)
    # engine host: (60 - 20 fetch + 5 observe) x 2 + 4 before, over 2
    assert got["engine_host_ms.single"] == pytest.approx(47.0)
    assert got["engine_wait_ms.single"] == pytest.approx(20.0)


def test_readers_leave_out_a_round_cut_by_the_deadline(monkeypatch):
    recs = _round(0, 1.0) + _round(10, 1.1, cut=True)
    got = _read(monkeypatch, recs, _run(1))
    assert got == pytest.approx({"driver_ms.single": 25.0,
                                 "engine_host_ms.single": 45.0,
                                 "engine_wait_ms.single": 20.0})


def test_readers_leave_out_spans_before_the_window(monkeypatch):
    early = [_rec(90, "engine.fetch", -5.0, -4.0)]
    got = _read(monkeypatch, early + _round(0, 1.0), _run(1))
    assert got["engine_wait_ms.single"] == pytest.approx(20.0)


@pytest.mark.parametrize("recs", [[], [_rec(0, "tuner.round", 1.0, None)]],
                         ids=["nothing", "open"])
def test_readers_read_nothing_without_spans(monkeypatch, recs):
    assert _read(monkeypatch, recs, _run(3)) == dict.fromkeys(READERS)


def test_readers_read_nothing_without_a_recorder(monkeypatch):
    monkeypatch.delattr(repro.obs, "records")
    for m in READERS:
        assert cells.metric_reader(m)(_run(3)) is None
