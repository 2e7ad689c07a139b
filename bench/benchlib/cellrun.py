"""One run of one cell: data from the seed, warm-up, the measured window, the
check, the metrics, and the result line."""
from __future__ import annotations

import dataclasses
import json
import os
import shutil
import sys
import tempfile
import time

import numpy as np

from . import cells, runtime, verdict


@dataclasses.dataclass
class Run:
    """What metric readers see."""
    setup_s: float
    window_s: float
    explorations: list
    rounds: list
    trace: dict | None = None


def _window(gen, ctx, seconds: float, monitor, *, trace_dir: str | None):
    """The measured window. Returns (explorations, seconds, compiles)."""
    import jax

    c0 = monitor.snapshot()
    t0 = time.monotonic()
    deadline = t0 + seconds
    closed = lambda: time.monotonic() >= deadline
    if trace_dir is None:
        exps = gen.run_explorations(ctx, closed)
        t1 = time.monotonic()
    else:
        with jax.profiler.trace(trace_dir):
            with jax.profiler.TraceAnnotation("window"):
                exps = gen.run_explorations(ctx, closed, spans=True)
            t1 = time.monotonic()
    c1 = monitor.snapshot()
    return exps, t1 - t0, (c1[0] - c0[0], c1[1] - c0[1], c1[2] - c0[2])


def _warm_up(gen, ctx, seconds: float, monitor) -> None:
    """Replay the window's own schedule (same seed, same keys) until it has
    run longer than the window will, not counting the time spent tracing,
    compiling or loading programs: every program the window meets is then
    loaded, whatever the speed of the commit."""
    budget = 1.25 * seconds + 2.0
    t0 = time.monotonic()
    s0 = monitor.seconds
    closed = lambda: (time.monotonic() - t0) - (monitor.seconds - s0) >= budget
    gen.run_explorations(ctx, closed)


def _trace_readings(trace_dir: str, layer_table: dict) -> dict | None:
    import trace_reduce

    path = trace_reduce.find_xplane(trace_dir)
    if path is None:
        return None
    return trace_reduce.reduce(trace_reduce.extract(path), layer_table)


def run(args, root: str, *, devices=None, bench_dir: str = cells.BENCH_DIR
        ) -> int:
    """One run. ``devices`` (tests only) skips the look for a chip."""
    t_proc = runtime.process_start_monotonic()
    cell = cells.load_cell(root, args.workload, bench_dir)
    devs = devices
    if devs is None:
        try:
            devs = runtime.require_tpu(cell.chips)
        except runtime.NoChip as e:
            print(f"bench: {e}", file=sys.stderr)
            return 3
    runtime.enable_compile_cache(root)
    monitor = runtime.CompileMonitor().install()

    t_dev = time.monotonic()
    gen = cell.generator
    ctx = gen.build_context(cell, args.seed)
    t_data = time.monotonic()
    c0 = monitor.snapshot()
    _warm_up(gen, ctx, args.seconds, monitor)
    c1 = monitor.snapshot()
    setup_s = time.monotonic() - t_proc
    print(json.dumps({"setup": {
        "to_chip_s": t_dev - t_proc, "data_s": t_data - t_dev,
        "warm_up_s": t_proc + setup_s - t_data,
        "warm_up_compiles": c1[0] - c0[0], "warm_up_loads": c1[1] - c0[1],
        "warm_up_traces": c1[2] - c0[2],
        "warm_up_program_s": c1[3] - c0[3]}}))

    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if args.trace else None
    try:
        exps, window_s, (compiles, loads, traces) = _window(
            gen, ctx, args.seconds, monitor, trace_dir=trace_dir)
        print(f"bench: window {window_s:.6f} s, programs compiled or loaded "
              f"in it {compiles} ({loads} from the cache), traced {traces}",
              file=sys.stderr)
        print(json.dumps({"window_compiles": compiles, "window_loads": loads,
                          "window_traces": traces}))
        device = runtime.device_record(devs)
        trace = None
        if trace_dir is not None:
            t_tr = time.monotonic()
            with open(os.path.join(bench_dir, "layers.json")) as f:
                trace = _trace_readings(trace_dir, json.load(f))
            print(json.dumps({"trace_reduce_s": time.monotonic() - t_tr}))
    finally:
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)

    rounds = [r for e in exps for r in e.rounds]
    the_run = Run(setup_s=setup_s, window_s=window_s, explorations=exps,
                  rounds=rounds, trace=trace)
    print(json.dumps({"explorations": len(exps), "rounds": len(rounds),
                      "final_adrs": [e.final_adrs for e in exps
                                     if e.final_adrs is not None]}))

    # the check, once the window's explorations have returned (their engines
    # are freed) and the memory peak has been read
    rng = np.random.default_rng([args.seed, 1])
    t_check = time.monotonic()
    rd = verdict.judge(ctx, gen, the_run.explorations,
                       n_check=int(cell.config["check"]["rounds"]), rng=rng)
    print(json.dumps({"check_s": time.monotonic() - t_check,
                      "checked_rounds": rd.checked_rounds,
                      "pick_rank_max": rd.pick_rank_max.get("program"),
                      "pick_gap": rd.pick_gap.get("program"),
                      "worst_round": rd.worst}))
    ok, checks = verdict.decide(rd, cell.config["check"]["limits"])

    metrics = {}
    wanted = cell.per_layer if args.trace else cell.end_to_end
    for m in wanted:
        value = cells.metric_reader(m["name"], bench_dir)(the_run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    if args.trace and trace is not None:
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]

    for why in rd.reasons:
        print(f"bench: failed: {why}", file=sys.stderr)
    print(f"bench: checked {rd.checked_rounds} rounds of {rd.attempted}",
          file=sys.stderr)
    for name, c in checks.items():
        print(f"bench: check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    line = {"correct": bool(ok), "attempted": rd.attempted,
            "failed": rd.failed, "metrics": metrics, "device": device}
    if args.trace and trace is not None:
        line["breakdown"] = {"device_ops": trace["top_ops"],
                             "idle_gaps": trace["idle_gaps"]}
    line["checks"] = checks
    sys.stdout.flush()
    print(json.dumps(line))
    return 0
