#!/usr/bin/env python3
"""Readings that the limits of the check are set from.

    python3 bench/control.py --workload <cell> --seeds 11,12,13 --seconds 10 \\
        [--faults none,pick_altered,...] [--at 6:33,38] [--out FILE]

For each seed, in one process: build the cell's data, drive the timed path
for ``--seconds`` (no warm-up: nothing here is timed), then read

* ``program``: the numbers the benchmark compares (``flow_rel_err``,
  ``pick_rank``), the diagnostic ones beside them (``pick_rank_max``,
  ``pick_gap``) and ``correct`` as the benchmark's comparison decides it,
  from what the timed path produced;
* the reference itself, put in the program's place one precision step
  lower — the GP algebra in float32 with matmuls at the TPU's ``high``
  (``f32_high``), the cost model with bfloat16 intermediates (``bf16``) —
  and the GP algebra in plain float32 (``f32``, the precision the
  configurations state), each judged by the float64 reference in the same
  way.

``--faults`` plants each fault of ``benchlib.faults.FAULTS`` named (and
``none`` for the sound program) under the timed path, on every seed;
``flow_bf16`` is the control in the flow's place. ``--at E:R1,R2`` checks
rounds R1, R2 of exploration E instead of the drawn sample, and drives the
window until that exploration has run them. ``--full-run`` instead makes a
whole benchmark run (``cellrun.run``: warm-up, window, check, result line)
with each fault planted, to show ``correct`` false as the benchmark decides
it. One JSON line per seed and
fault, then a summary line with the largest sound reading and the smallest
control reading of each number. Needs a TPU, like the benchmark.
"""
import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def readings(cell, seed: int, seconds: float, fault: str | None = None,
             monitor=None, at: dict | None = None) -> dict:
    """One seed's readings; the timed path runs for ``seconds`` not
    counting the time ``monitor`` (a ``CompileMonitor``) saw spent on
    programs, so a cold process still covers as many rounds."""
    import numpy as np

    from benchlib import faults, reference as R, verdict

    gen = cell.generator
    ctx = gen.build_context(cell, seed)
    t0 = time.monotonic()
    s0 = monitor.seconds if monitor is not None else 0.0
    spent = lambda: (time.monotonic() - t0) - (
        monitor.seconds - s0 if monitor is not None else 0.0)
    exps: list = []

    def closed():
        if at is None:
            return spent() >= seconds
        (e, rounds), = at.items()
        done = [x for x in exps if x.index == e]
        return bool(done) and len(done[0].rounds) > max(rounds)

    with faults.planted(fault):
        gen.run_explorations(ctx, closed, out=exps)
    controls = at is not None or fault is None
    t_check = time.monotonic()
    rd = verdict.judge(
        ctx, gen, exps, n_check=int(cell.config["check"]["rounds"]),
        rng=np.random.default_rng([seed, 1]), at=at,
        gp_controls=(R.Arith("f32"), R.Arith("f32_high")) if controls else (),
        flow_controls=(R.Arith("bf16"),) if controls else ())
    ok, checks = verdict.decide(rd, cell.config["check"]["limits"])
    return {"seed": seed, "fault": fault, "correct": ok, "checks": checks,
            "attempted": rd.attempted, "failed": rd.failed,
            "reasons": rd.reasons, "checked_rounds": rd.checked_rounds,
            "check_s": time.monotonic() - t_check,
            "flow_rel_err": rd.flow_rel_err, "pick_rank": rd.pick_rank,
            "pick_rank_max": rd.pick_rank_max, "pick_gap": rd.pick_gap,
            "ranks": rd.ranks, "worst": rd.worst}


def summarize(lines: list) -> dict:
    out = {}
    for num in ("flow_rel_err", "pick_rank", "pick_rank_max", "pick_gap"):
        sound = [ln[num]["program"] for ln in lines
                 if ln["fault"] is None and "program" in ln[num]]
        low = [v for ln in lines for k, v in ln[num].items()
               if (k == "program" and ln["fault"] is not None)
               or k in ("f32_high", "bf16")]
        out[num] = {"sound_max": max(sound, default=None),
                    "control_or_fault_min": min(low, default=None)}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--faults", default="none",
                    help="comma-separated: none and/or benchlib.faults.FAULTS")
    ap.add_argument("--at", help="E:R1,R2 — check these rounds of "
                                 "exploration E")
    ap.add_argument("--full-run", action="store_true",
                    help="a whole benchmark run per fault and seed")
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    sys.path[:0] = [HERE, os.path.join(ROOT, "src")]
    from benchlib import cells, runtime

    cell = cells.load_cell(ROOT, args.workload)
    try:
        runtime.require_tpu(cell.chips)
    except runtime.NoChip as e:
        print(f"control: {e}", file=sys.stderr)
        return 3
    if args.full_run:
        from benchlib import cellrun, faults

        for f in args.faults.split(","):
            for s in args.seeds.split(","):
                print(json.dumps({"full_run": {"fault": f, "seed": int(s)}}),
                      flush=True)
                run = argparse.Namespace(workload=args.workload, seed=int(s),
                                         seconds=args.seconds, trace=0)
                with faults.planted(None if f == "none" else f):
                    cellrun.run(run, ROOT)
                sys.stdout.flush()
        return 0
    runtime.enable_compile_cache(ROOT)
    monitor = runtime.CompileMonitor().install()
    at = None
    if args.at:
        e, rounds = args.at.split(":")
        at = {int(e): {int(r) for r in rounds.split(",")}}
    lines = []
    for f in args.faults.split(","):
        for s in args.seeds.split(","):
            ln = readings(cell, int(s), args.seconds,
                          None if f == "none" else f, monitor, at)
            lines.append(ln)
            print(json.dumps(ln), flush=True)
    summary = summarize(lines)
    print(json.dumps({"summary": summary}))
    if args.out:
        with open(args.out, "w") as f:
            for ln in lines + [{"summary": summary}]:
                f.write(json.dumps(ln) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
