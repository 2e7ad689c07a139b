"""Reduce a profiler trace of one window to the benchmark's device numbers.

Two steps, so that the arithmetic can be checked on a small recorded trace:

* :func:`extract` reads the ``.xplane.pb`` the JAX profiler wrote and keeps
  what the reduction needs: the device's XLA module and op events, and the
  harness's own host spans (``window``, ``exploration``, ``prologue``,
  ``round``, ``flow``);
* :func:`reduce` computes, inside the ``window`` span:
  - ``busy_s``: the union of the device's op intervals (averaged over the
    devices traced), and ``window_s``, the span's length;
  - ``modules``: device seconds per XLA module, and ``layers``: the same
    grouped by the name table ``layers.json`` (a module whose name matches
    no entry counts under ``other``);
  - ``top_ops``: the ten device operations that took most time;
  - ``idle_gaps``: device idle time summed by what the host was doing, the
    innermost harness span around each gap's middle, the ten largest.

Run as a script on a trace directory to print the reduction.
"""
from __future__ import annotations

import glob
import gzip
import json
import os
import re
import sys

HOST_SPANS = ("window", "exploration", "prologue", "round", "flow")
_DEPTH = {name: i for i, name in enumerate(HOST_SPANS)}


def find_xplane(trace_dir: str) -> str | None:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    return found[-1] if found else None


def extract(path: str) -> dict:
    """``{"devices": {plane: {"modules": [...], "ops": [...]}}, "spans":
    [...]}``; every event is ``[name, start_ns, duration_ns]``."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices, spans = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = {"modules": [], "ops": []}
            for line in plane.lines:
                kind = {"XLA Modules": "modules",
                        "XLA Ops": "ops"}.get(line.name)
                if kind is None:
                    continue
                dev[kind] += [[e.name, e.start_ns, e.duration_ns]
                              for e in line.events]
            devices[plane.name] = dev
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [[e.name, e.start_ns, e.duration_ns]
                          for e in line.events if e.name in _DEPTH]
    return {"devices": devices, "spans": spans}


def _union(intervals) -> list:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi) -> list:
    return [[max(s, lo), min(e, hi)] for s, e in intervals
            if e > lo and s < hi]


_MODULE = re.compile(r"^(?:jit_)?(.*?)(?:\(\d+\))?$")


def module_name(name: str) -> str:
    """``jit__round_seq(12)`` -> ``_round_seq``."""
    return _MODULE.match(name).group(1)


def classify(name: str, layer_table: dict) -> str:
    fn = module_name(name)
    for layer, fns in layer_table.items():
        if fn in fns:
            return layer
    return "other"


def reduce(events: dict, layer_table: dict) -> dict:
    windows = [s for s in events["spans"] if s[0] == "window"]
    if not windows or not events["devices"]:
        return None
    _, w0, wd = max(windows, key=lambda s: s[2])
    w1 = w0 + wd
    busy_ns, modules, ops = [], {}, {}
    per_dev_busy = {}
    for plane, dev in events["devices"].items():
        src = dev["ops"] or dev["modules"]
        iv = _union(_clip([[s, s + d] for _, s, d in src], w0, w1))
        per_dev_busy[plane] = iv
        busy_ns.append(sum(e - s for s, e in iv))
        for name, s, d in dev["modules"]:
            lo, hi = max(s, w0), min(s + d, w1)
            if hi > lo:
                key = module_name(name)
                modules[key] = modules.get(key, 0.0) + (hi - lo) * 1e-9
        for name, s, d in dev["ops"]:
            lo, hi = max(s, w0), min(s + d, w1)
            if hi > lo:
                ops[name] = ops.get(name, 0.0) + (hi - lo) * 1e-9
    layers: dict = {}
    for name, sec in modules.items():
        layer = classify(name, layer_table)
        layers[layer] = layers.get(layer, 0.0) + sec

    # idle gaps of the first device, by the innermost host span at their middle
    spans = [s for s in events["spans"] if s[0] != "window"]
    first = per_dev_busy[sorted(per_dev_busy)[0]]
    gaps, prev = [], w0
    for s, e in first + [[w1, w1]]:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    # sweep the span edges and the gaps' middles in time order, keeping how
    # many spans of each kind are open
    edges = sorted([(s, 1, n) for n, s, _ in spans]
                   + [(s + d, -1, n) for n, s, d in spans])
    open_ = [0] * len(HOST_SPANS)
    open_[0] = 1
    idle: dict = {}
    k = 0
    for g0, g1 in sorted(gaps):
        mid = (g0 + g1) / 2
        while k < len(edges) and edges[k][0] <= mid:
            open_[_DEPTH[edges[k][2]]] += edges[k][1]
            k += 1
        where = HOST_SPANS[max(i for i, c in enumerate(open_) if c > 0)]
        idle[where] = idle.get(where, 0.0) + (g1 - g0) * 1e-9
    top = lambda d: [[k, v] for k, v in sorted(d.items(),
                                               key=lambda kv: -kv[1])[:10]]
    return {"busy_s": sum(busy_ns) / len(busy_ns) * 1e-9,
            "window_s": wd * 1e-9, "modules": modules, "layers": layers,
            "top_ops": top(ops), "idle_gaps": top(idle)}


def save_events(events: dict, path: str) -> None:
    """Write :func:`extract`'s output, as the recorded test trace
    ``tests/data/v5e_paper_events.json.gz`` was written (a half-second
    traced window of ``paper-2500.single`` on a TPU v5e)."""
    with gzip.open(path, "wt") as f:
        json.dump(events, f)


def load_events(path: str) -> dict:
    with gzip.open(path, "rt") as f:
        return json.load(f)


if __name__ == "__main__":
    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "layers.json")) as f:
        table = json.load(f)
    ev = extract(find_xplane(sys.argv[1]))
    print(json.dumps(reduce(ev, table), indent=1))
