"""The check that decides ``correct``: a sound run passes, the control fails,
and every fault a single-exploration cell can have is caught.

Each test drives the rest of a real run (data, warm-up, window, check,
result line) on the CPU at a small size, skipping only the look for a chip;
the faults are planted under the timed path with ``benchlib.faults``.
"""
import argparse
import json

import jax
import numpy as np
import pytest

import control
from benchlib import cellrun, cells, faults, reference as R


def _run(root, capsys, cell, seed, fault=None, seconds=4.0):
    args = argparse.Namespace(workload=cell, seed=seed, seconds=seconds,
                              trace=0)
    with faults.planted(fault):
        rc = cellrun.run(args, str(root), devices=jax.devices("cpu"))
    assert rc == 0
    out = capsys.readouterr()
    line = json.loads(out.out.strip().splitlines()[-1])
    assert list(line)[-1] == "checks"
    assert "limit" in out.err.strip().splitlines()[-1]
    return line


@pytest.mark.parametrize("cell", ["paper-2500.single", "pool100k.single"])
def test_sound_run_is_correct(tiny_root, capsys, cell):
    line = _run(tiny_root, capsys, cell, 2147483911)
    assert line["correct"] is True, line
    assert line["failed"] == 0 and line["attempted"] > 0
    assert {"round_s", "setup_s"} <= set(line["metrics"])
    for c in line["checks"].values():
        assert c["value"] <= c["limit"]


@pytest.mark.parametrize("fault", faults.FAULTS)
def test_planted_fault_is_not_correct(tiny_root, capsys, fault):
    line = _run(tiny_root, capsys, "paper-2500.single", 2147483912, fault)
    assert line["correct"] is False, (fault, line)


@pytest.mark.parametrize("cell", ["paper-2500.single", "pool100k.single"])
def test_a_fault_on_refactor_rounds_fails_the_pick_rank(tiny_root, capsys,
                                                        cell):
    """A fault of a path that runs on a few rounds only is caught by the
    engine's number, not by a duplicate or the flow."""
    # a window long enough for whole explorations, each with its refactors
    line = _run(tiny_root, capsys, cell, 2147483913, "refactor_altered",
                seconds=12.0)
    assert line["attempted"] >= 16, line
    assert line["correct"] is False and line["failed"] == 0, line
    checks = line["checks"]
    assert checks["flow_rel_err"]["value"] <= checks["flow_rel_err"]["limit"]
    assert checks["pick_rank"]["value"] > checks["pick_rank"]["limit"]


def test_control_fails_the_limits(tiny_root):
    """The reference one precision step lower: its flow metrics (bfloat16
    intermediates) exceed the limit, while the program's pass; the GP
    arithmetics' own picks are judged beside the program's."""
    cell = cells.load_cell(str(tiny_root), "paper-2500.single")
    limits = cell.config["check"]["limits"]
    for seed in (11, 12, 13):
        rd = control.readings(cell, seed, 3.0)
        assert rd["failed"] == 0 and rd["correct"] is True
        assert rd["flow_rel_err"]["program"] <= limits["flow_rel_err"]
        assert rd["flow_rel_err"]["bf16"] > limits["flow_rel_err"]
        assert rd["pick_rank"]["program"] <= limits["pick_rank"]
        assert set(rd["pick_rank"]) == {"program", "f32", "f32_high"}


def test_reading_at_named_rounds(tiny_root):
    cell = cells.load_cell(str(tiny_root), "pool100k.single")
    rd = control.readings(cell, 2147483914, 0.0, at={1: {2, 5}})
    assert rd["checked_rounds"] == 2 and rd["failed"] == 0
    assert len(rd["ranks"]["f32"]) == 2


def test_reference_cost_model_control_is_coarser():
    pool = R.sample_pool(5, 200)
    for w in R.WORKLOADS:
        y = R.soc_metrics(pool, R.workload(w))
        yb = R.soc_metrics(pool, R.workload(w), R.Arith("bf16"))
        assert np.all(np.isfinite(y)) and np.all(y > 0)
        assert np.max(np.abs(yb - y) / y) > 1e-3
