"""Fleet exploration: several SoC-design scenarios in one batched run.

    PYTHONPATH=src python examples/fleet.py

Three scenarios share one candidate pool and one memoized evaluation cache:
two seeds of ResNet-50 (seed-robustness of the learned front) plus a
latency-weighted Transformer scenario (the acquisition spends its information
budget on the latency objective). Each round fits ALL scenarios' GPs and
scores ALL candidates in a single vmapped XLA program.
"""
import jax
import numpy as np

from repro.core import FleetScenario, fleet_tuner, make_space, pareto_front
from repro.soc import VLSIFlow


def main():
    space = make_space()                       # the paper's TABLE I space
    pool = np.asarray(space.sample(jax.random.PRNGKey(0), 500))

    # true fronts (cheap surrogate makes this possible) for ADRS reporting
    refs = {w: pareto_front(VLSIFlow(space, w)(pool))
            for w in ("resnet50", "transformer")}

    scenarios = [
        FleetScenario("resnet50", seed=0),
        FleetScenario("resnet50", seed=1),
        FleetScenario("transformer", seed=0, weights=(3.0, 1.0, 1.0)),
    ]
    fr = fleet_tuner(space, pool, scenarios, T=10, n=16, b=10,
                     reference_fronts=refs, verbose=True)

    for sc, res in zip(fr.scenarios, fr.results):
        y = res.pareto_y[np.argsort(res.pareto_y[:, 0])]
        print(f"\n{sc.label}: final ADRS {res.history[-1]['adrs']:.4f}, "
              f"{len(y)} Pareto designs (latency ms, power mW, area mm^2):")
        for row in y[:5]:
            print(f"  {row[0]:8.3f}  {row[1]:8.1f}  {row[2]:7.2f}")

    print(f"\n{fr.cache.summary()}")
    print(f"fleet wall time: {fr.wall_s:.1f}s for {len(scenarios)} scenarios")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
