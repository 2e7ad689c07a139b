#!/usr/bin/env python3
"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, traffic mix and metrics are found by name from
``BENCHMARK.json`` at the checkout root. The run refuses to start without a
TPU (exit 3), builds its data from ``--seed``, warms up every program the
window will use, drives the program's user entry for ``--seconds``, checks
what the window produced against the plain reference, and prints one JSON
line last on standard output. ``--trace 1`` runs the window under the
profiler and reports the per-layer metrics instead of the end-to-end ones.
"""
import argparse
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path[:0] = [HERE, os.path.join(ROOT, "src")]
    from benchlib import cellrun

    return cellrun.run(args, ROOT)


if __name__ == "__main__":
    sys.exit(main())
