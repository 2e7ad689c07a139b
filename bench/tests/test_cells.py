"""Cells, configurations, traffic mixes, generators and metrics are found by
name, and a new cell needs new files and a BENCHMARK.json entry only."""
import argparse
import json
import os
import types

import jax

from benchlib import cellrun, cells, drive

from conftest import BENCH, ROOT


def test_every_cell_loads_with_its_files():
    bench = cells.load_benchmark(ROOT)
    for w in bench["workloads"]:
        cell = cells.load_cell(ROOT, w["name"])
        assert cell.config["name"] == w["config"]
        assert cell.generator is drive.generator(cell.traffic["generator"])
        for fn in ("build_context", "run_explorations", "key_rounds",
                   "replay_exploration"):
            assert callable(getattr(cell.generator, fn))
        assert any(m["name"] == "setup_s" for m in cell.end_to_end)
        assert len(cell.end_to_end) >= 2 and cell.per_layer
        for m in cell.end_to_end + cell.per_layer:
            assert callable(cells.metric_reader(m["name"]))


def test_each_metric_lists_only_cells_that_report_its_moves():
    bench = cells.load_benchmark(ROOT)
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        for w in m["workloads"]:
            target = e2e[m["moves"]]
            assert "workloads" not in target or w in target["workloads"]


def test_config_files_hold_the_source_and_limits():
    bench = cells.load_benchmark(ROOT)
    for c in bench["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["name"] == c["name"]
        assert set(cfg["check"]["limits"]) == {"flow_rel_err", "pick_rank"}
        assert cfg["reduced"] == c["reduced"]
        # every setting the reference replays comes from protocol or engine
        assert set(cfg) >= {"protocol", "engine"} and "reference" not in cfg


def test_a_cell_of_new_files_only_loads(bench_copy):
    root = bench_copy.parent
    (bench_copy / "configs" / "paper-5000.json").write_text(json.dumps(
        {**json.load(open(os.path.join(BENCH, "configs", "paper-2500.json"))),
         "name": "paper-5000", "n_pool": 5000}))
    (bench_copy / "traffic" / "single-mobilenet.json").write_text(json.dumps(
        {"generator": "soc_tuner", "workload": "mobilenet"}))
    (bench_copy / "metrics" / "rounds_per_s.py").write_text(
        "def read(run):\n    return len(run.rounds) / run.window_s\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "paper-5000", "source": "x",
                             "file": "bench/configs/paper-5000.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "paper-5000.mobilenet",
                               "config": "paper-5000",
                               "traffic": "single-mobilenet", "chips": 1,
                               "why": "x"})
    bench["end_to_end"].append({"name": "rounds_per_s", "unit": "rounds/s",
                                "better": "higher", "bound": 0.05,
                                "source": "host_clock",
                                "workloads": ["paper-5000.mobilenet"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = cells.load_cell(str(root), "paper-5000.mobilenet", str(bench_copy))
    assert cell.config["n_pool"] == 5000
    assert cell.traffic["workload"] == "mobilenet"
    names = [m["name"] for m in cell.end_to_end]
    assert names == ["setup_s", "rounds_per_s"]
    read = cells.metric_reader("rounds_per_s", str(bench_copy))
    run = types.SimpleNamespace(rounds=[1, 2, 3], window_s=2.0)
    assert read(run) == 1.5
    # the cells already there still load, with their metrics
    old = cells.load_cell(str(root), "paper-2500.single", str(bench_copy))
    assert "rounds_per_s" not in [m["name"] for m in old.end_to_end]


ECHO = """\
import os

from benchlib import drive

_base = drive.generator("soc_tuner", os.path.dirname(__file__))
build_context = _base.build_context
key_rounds = _base.key_rounds
replay_exploration = _base.replay_exploration
SEEDS = []


def run_explorations(ctx, closed, *, spans=False, out=None):
    SEEDS.append(ctx.seed)
    return _base.run_explorations(ctx, closed, spans=spans, out=out)
"""


def test_a_mix_with_a_generator_of_its_own_runs(bench_copy, tiny_root,
                                                capsys):
    """A mix that needs another way of driving the program adds a generator
    module and a traffic file; a whole run of its cell uses them."""
    (bench_copy / "generators" / "soc_tuner_echo.py").write_text(ECHO)
    (bench_copy / "traffic" / "echo.json").write_text(json.dumps(
        {"generator": "soc_tuner_echo", "workload": "mobilenet"}))
    bench = json.loads((tiny_root / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "paper-2500.echo",
                               "config": "paper-2500", "traffic": "echo",
                               "chips": 1, "why": "x"})
    bench["end_to_end"].append({"name": "rounds_per_s", "unit": "rounds/s",
                                "better": "higher", "bound": 0.05,
                                "source": "host_clock",
                                "workloads": ["paper-2500.echo"]})
    (tiny_root / "BENCHMARK.json").write_text(json.dumps(bench))
    (bench_copy / "metrics" / "rounds_per_s.py").write_text(
        "def read(run):\n    return len(run.rounds) / run.window_s\n")
    args = argparse.Namespace(workload="paper-2500.echo", seed=2147483921,
                              seconds=3.0, trace=0)
    rc = cellrun.run(args, str(tiny_root), devices=jax.devices("cpu"),
                     bench_dir=str(bench_copy))
    assert rc == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is True, line
    assert set(line["metrics"]) == {"setup_s", "rounds_per_s"}
    echo = drive.generator("soc_tuner_echo", str(bench_copy / "generators"))
    assert echo.SEEDS == [2147483921, 2147483921]     # warm-up and window
