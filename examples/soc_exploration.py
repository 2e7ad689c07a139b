"""Full SoC exploration for a target DNN — the paper's end-to-end use case.

Explores the TABLE I space for a chosen workload (paper benchmarks or any of
the 10 assigned LM architectures lowered to a systolic workload), compares
SoC-Tuner against a baseline, and prints the balanced optimum.

    PYTHONPATH=src python examples/soc_exploration.py --workload qwen3-14b:decode
"""
import argparse

import jax
import numpy as np

from repro.core import adrs, make_space, pareto_front, run_baseline, soc_tuner
from repro.soc import VLSIFlow


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="transformer",
                    help="resnet50 | mobilenet | transformer | <arch>[:mode]")
    ap.add_argument("--pool", type=int, default=1500)
    ap.add_argument("--T", type=int, default=25)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    space = make_space()
    key = jax.random.PRNGKey(args.seed)
    pool = np.asarray(space.sample(key, args.pool))
    flow = VLSIFlow(space, args.workload)
    ref = pareto_front(flow(pool))

    print(f"== SoC-Tuner on {args.workload} ==")
    ours = soc_tuner(space, pool, flow, T=args.T, reference_front=ref,
                     key=key, verbose=True)
    print(f"== random baseline ==")
    base = run_baseline("random", space, pool, VLSIFlow(space, args.workload),
                        T=args.T, key=key, reference_front=ref)
    print(f"\nADRS   soc-tuner={ours.history[-1]['adrs']:.4f}   "
          f"random={base.history[-1]['adrs']:.4f}")

    front = ours.pareto_y
    z = (front - front.min(0)) / np.maximum(np.ptp(front, 0), 1e-12)
    pick = int(np.argmin(np.linalg.norm(z, axis=1)))
    idx = ours.pareto_idx(pool)[pick]
    print(f"\nBalanced optimum for {args.workload} "
          f"(lat={front[pick, 0]:.3f}ms, p={front[pick, 1]:.0f}mW, "
          f"a={front[pick, 2]:.2f}mm2):")
    for name, val in zip(space.names(), space.values(idx[None, :])[0]):
        print(f"  {name:<10s} {val:g}")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
