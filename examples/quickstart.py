"""Quickstart: explore a small SoC design pool with SoC-Tuner in ~a minute.

    PYTHONPATH=src python examples/quickstart.py
"""
import jax
import numpy as np

from repro.core import make_space, pareto_front, soc_tuner
from repro.soc import VLSIFlow


def main():
    space = make_space()                       # the paper's TABLE I space
    key = jax.random.PRNGKey(0)
    pool = np.asarray(space.sample(key, 500))  # candidate designs
    flow = VLSIFlow(space, "resnet50")         # latency/power/area evaluator

    # reference front (only possible because our flow is cheap; the paper's
    # VLSI flow takes hours per design) — separate flow so the tuner's
    # evaluation budget is counted honestly
    ref = pareto_front(VLSIFlow(space, "resnet50")(pool))

    result = soc_tuner(space, pool, flow, T=15, n=20, b=12,
                       reference_front=ref, key=key, verbose=True)

    print("\nLearned Pareto-optimal SoC designs (latency ms, power mW, mm^2):")
    for y in result.pareto_y[np.argsort(result.pareto_y[:, 0])][:8]:
        print(f"  {y[0]:8.3f}  {y[1]:8.1f}  {y[2]:7.2f}")
    best = result.pareto_idx(pool)[np.argmin(result.pareto_y[:, 0])]
    names = space.names()
    vals = space.values(best[None, :])[0]
    print("\nFastest design found:")
    for n_, v in zip(names, vals):
        print(f"  {n_:<10s} {v:g}")
    print(f"\nflow evaluations used: {flow.evaluated} "
          f"(vs {len(pool)} for exhaustive search)")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
