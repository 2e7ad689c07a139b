"""The reduction from a profiler trace to busy time, module time by layer and
idle gaps by host span."""
import json
import os

import pytest

import trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
TABLE = {"engine": ["_fit", "_round_seq"], "flow": ["soc_metrics"]}


def _events():
    ms = 1_000_000
    return {
        "devices": {
            "/device:TPU:0": {
                "modules": [["jit__fit(3)", 10 * ms, 30 * ms],
                            ["jit_soc_metrics(7)", 60 * ms, 10 * ms],
                            ["jit_other(1)", 80 * ms, 5 * ms]],
                "ops": [["fusion.1", 10 * ms, 20 * ms],
                        ["cholesky", 25 * ms, 15 * ms],   # overlaps
                        ["fusion.2", 60 * ms, 10 * ms],
                        ["copy", 80 * ms, 5 * ms],
                        ["late", 95 * ms, 20 * ms]],      # cut at 100 ms
            }},
        "spans": [["window", 0, 100 * ms],
                  ["exploration", 0, 100 * ms],
                  ["round", 5 * ms, 50 * ms],
                  ["flow", 55 * ms, 22 * ms],
                  ["prologue", 78 * ms, 22 * ms]],
    }


def test_reduce_arithmetic():
    r = trace_reduce.reduce(_events(), TABLE)
    assert r["window_s"] == pytest.approx(0.1)
    # union: [10,40] + [60,70] + [80,85] + [95,100] = 50 ms
    assert r["busy_s"] == pytest.approx(0.050)
    assert r["layers"]["engine"] == pytest.approx(0.030)
    assert r["layers"]["flow"] == pytest.approx(0.010)
    assert r["layers"]["other"] == pytest.approx(0.005)
    assert r["top_ops"][0] == ["fusion.1", pytest.approx(0.020)]
    idle = dict(r["idle_gaps"])
    # gaps [0,10] mid 5: round opens at 5 -> round; [40,60] mid 50 -> round;
    # [70,80] mid 75 -> flow; [85,95] mid 90 -> prologue
    assert idle["round"] == pytest.approx(0.030)
    assert idle["flow"] == pytest.approx(0.010)
    assert idle["prologue"] == pytest.approx(0.010)
    assert sum(idle.values()) == pytest.approx(0.1 - 0.050)


def test_module_names():
    assert trace_reduce.module_name("jit__round_seq(12)") == "_round_seq"
    assert trace_reduce.module_name("jit_soc_metrics") == "soc_metrics"
    assert trace_reduce.classify("jit__fit(1)", TABLE) == "engine"
    assert trace_reduce.classify("jit_foo(1)", TABLE) == "other"


def test_no_window_no_reading():
    ev = _events()
    ev["spans"] = [s for s in ev["spans"] if s[0] != "window"]
    assert trace_reduce.reduce(ev, TABLE) is None


def test_recorded_v5e_trace():
    """A half-second traced window of paper-2500.single recorded on a TPU v5e
    (the harness's spans and the device's module and op events, as
    `trace_reduce.extract` keeps them)."""
    ev = trace_reduce.load_events(
        os.path.join(HERE, "data", "v5e_paper_events.json.gz"))
    assert list(ev["devices"]) == ["/device:TPU:0"]
    with open(os.path.join(os.path.dirname(HERE), "layers.json")) as f:
        table = json.load(f)
    r = trace_reduce.reduce(ev, table)
    assert 0.0 < r["busy_s"] < r["window_s"]
    assert r["layers"]["engine"] > 0.0
    assert set(r["modules"]) >= {"_fit", "frontier_maxima", "gp_predict"}
    idle = sum(v for _, v in r["idle_gaps"])
    assert idle == pytest.approx(r["window_s"] - r["busy_s"], rel=1e-6)
    assert {name for name, _ in r["idle_gaps"]} <= set(trace_reduce.HOST_SPANS)
