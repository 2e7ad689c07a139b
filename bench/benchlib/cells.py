"""Find a cell, its configuration, its traffic mix and its metrics by name.

Everything a cell needs sits in files of its own, found from the names in
``BENCHMARK.json``:

* the configuration's ``file`` (``bench/configs/<config>.json``) — the
  deployment: protocol parameters, the engine settings its source names and
  the limits of the check;
* ``bench/traffic/<traffic>.json`` — the mix's parameters, naming the
  generator that drives the entry, ``bench/generators/<generator>.py``;
* ``bench/metrics/<metric>.py`` — one reader per metric, ``read(run)``.

A new cell, configuration, mix or metric is added by new files and a
``BENCHMARK.json`` entry; nothing here changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os

from . import drive

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    generator: object     # the traffic's generator module
    end_to_end: list      # metric entries of BENCHMARK.json this cell reports
    per_layer: list


def load_benchmark(root: str) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _reports(metric: dict, cell: str, e2e_of_cell: set) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    # without a list, a per-layer metric goes wherever its end-to-end
    # metric is reported; an end-to-end metric without one is everywhere
    moves = metric.get("moves")
    return moves is None or moves in e2e_of_cell


def load_cell(root: str, name: str, bench_dir: str = BENCH_DIR) -> Cell:
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"unknown workload {name!r}; cells: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg_entry = configs[w["config"]]
    config = _load_json(os.path.join(root, cfg_entry["file"]))
    traffic = _load_json(os.path.join(bench_dir, "traffic",
                                      w["traffic"] + ".json"))
    e2e = [m for m in bench["end_to_end"] if _reports(m, name, set())]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"] if _reports(m, name, names)]
    gen = drive.generator(traffic["generator"],
                          os.path.join(bench_dir, "generators"))
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic, generator=gen, end_to_end=e2e,
                per_layer=per_layer)


def metric_reader(metric: str, bench_dir: str = BENCH_DIR):
    """The ``read(run)`` function of ``metrics/<metric>.py``."""
    path = os.path.join(bench_dir, "metrics", metric + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
