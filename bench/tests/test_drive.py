"""The flow tap counts rounds, leaves the prologue out and closes the
window; the round metrics cover every round of a window with a stall."""
import time
import types

import numpy as np
import pytest

from benchlib import cells, drive


def _flow(idx):
    return np.ones((len(idx), 3))


def test_tap_counts_rounds_after_the_prologue():
    tap = drive.FlowTap(_flow, closed=lambda: False, prologue=2)
    exp = drive.Exploration(0, None, time.monotonic(), "resnet50", 2)
    tap.begin(exp)
    tap(np.zeros((30, 26), int))          # ICD trials
    tap(np.zeros((20, 26), int))          # TED init
    assert exp.rounds == []
    for _ in range(3):
        tap(np.zeros((1, 26), int))
    tap.end()
    assert [r.index for r in exp.rounds] == [0, 1, 2]
    assert [r.call for r in exp.rounds] == [2, 3, 4]
    assert exp.rounds[0].t_start == exp.calls[1].t1
    for a, b in zip(exp.rounds, exp.rounds[1:]):
        assert b.t_start == a.t_end
    assert all(r.flow_s <= r.seconds for r in exp.rounds)


def test_tap_closes_the_window_at_the_deadline():
    state = {"closed": False}
    tap = drive.FlowTap(_flow, closed=lambda: state["closed"], prologue=2)
    exp = drive.Exploration(0, None, time.monotonic(), "resnet50", 2)
    tap.begin(exp)
    for _ in range(4):
        tap(np.zeros((1, 26), int))
    state["closed"] = True
    with pytest.raises(drive.WindowClosed):
        tap(np.zeros((1, 26), int))
    tap.end()
    assert len(exp.calls) == 4 and len(exp.rounds) == 2


def _run_with(durations):
    rounds = [drive.Round(0, i, 0.0, d, 0.0) for i, d in enumerate(durations)]
    return types.SimpleNamespace(rounds=rounds, window_s=sum(durations),
                                 setup_s=1.0, trace=None)


def test_round_metrics_count_a_stall():
    durations = [0.1] * 99 + [5.0]          # one stalled round
    run = _run_with(durations)
    mean = cells.metric_reader("round_s")(run)
    assert mean == pytest.approx(sum(durations) / 100)
    p95 = cells.metric_reader("round_p95_s")(run)
    assert p95 == pytest.approx(np.percentile(durations, 95))
    run = _run_with([0.1] * 90 + [5.0] * 10)
    assert cells.metric_reader("round_p95_s")(run) == pytest.approx(5.0)


def test_host_split_of_a_round():
    rounds = [drive.Round(0, 0, 0.0, 1.0, 0.25), drive.Round(0, 1, 1.0, 1.5,
                                                              0.25)]
    run = types.SimpleNamespace(rounds=rounds, trace=None)
    assert cells.metric_reader("select_ms.single")(run) == pytest.approx(500.0)
    assert cells.metric_reader("flow_ms.single")(run) == pytest.approx(250.0)
    assert cells.metric_reader("engine_device_ms.single")(run) is None
    assert cells.metric_reader("device_idle_share.single")(run) is None
