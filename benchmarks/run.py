"""Benchmark driver: one entry per paper table/figure + roofline summary.

``python -m benchmarks.run``          — CI-scale (small T/repeats, ~minutes)
``python -m benchmarks.run --full``   — paper-scale protocol (T=40, 10 seeds)

Prints ``name,value`` CSV lines; per-figure CSVs land in results/benchmarks/.
"""
from __future__ import annotations

import argparse
import sys
import time


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--skip-explore", action="store_true",
                    help="only fig5 + roofline + throughput (fast)")
    args = ap.parse_args()
    T = 40 if args.full else 12
    repeats = 10 if args.full else 2
    n_pool = 2500
    t0 = time.time()
    out: list[tuple[str, float]] = []

    print("== fig5: ICD importance & pruning ==")
    from . import fig5_importance
    r5 = fig5_importance.main()
    out += [("fig5.pinned_at_paper_vth", r5["pinned"]),
            ("fig5.calibrated_removal_pct", round(r5["removal_calibrated_pct"], 2))]

    print("== evaluator throughput ==")
    from . import eval_throughput
    out.append(("eval.designs_per_s", round(eval_throughput.main(), 1)))

    if not args.skip_explore:
        print(f"== fig7a: ADRS curves (T={T}, repeats={repeats}) ==")
        from . import fig7_adrs
        s7 = fig7_adrs.main(T=T, repeats=repeats, n_pool=n_pool)
        for m, (adrs, _) in s7.items():
            out.append((f"fig7a.final_adrs.{m}", round(adrs, 4)))

        print("== fig4ab: learned Pareto fronts ==")
        from . import fig4_pareto
        s4 = fig4_pareto.main(T=T, n_pool=n_pool)
        for m, v in s4.items():
            out.append((f"fig4.adrs.{m}", round(v, 4)))

        print("== fig4c: simplified-model gap ==")
        g = fig4_pareto.simplified_gap(T=T, n_pool=n_pool)
        out += [("fig4c.rel_error_pct", round(g["rel_error"] * 100, 1)),
                ("fig4c.adrs_simplified", round(g["adrs_simplified"], 4)),
                ("fig4c.adrs_full", round(g["adrs_full"], 4))]

        print("== fig6: inference latency across DNNs ==")
        from . import fig6_cycles
        fig6_cycles.main(T=T, n_pool=n_pool)

        print("== fig7b: area breakdown ==")
        fig7_adrs.breakdown(T=T)

    print("== roofline summary (from dry-run artifacts) ==")
    try:
        from . import roofline
        cells = roofline.load_cells("single")
        ok = [c for c in cells if c["status"] == "ok"]
        if ok:
            fracs = []
            for c in ok:
                t = roofline.terms(c)
                fracs.append((t["roofline_frac"], c["arch"], c["shape"]))
            fracs.sort(reverse=True)
            out.append(("roofline.cells_ok", len(ok)))
            out.append(("roofline.best_frac_pct",
                        round(fracs[0][0] * 100, 1)))
            out.append(("roofline.median_frac_pct",
                        round(fracs[len(fracs) // 2][0] * 100, 1)))
            print(f"  {len(ok)} cells; best {fracs[0][1]}/{fracs[0][2]} "
                  f"at {fracs[0][0]*100:.1f}% of roofline")
        else:
            print("  (no dry-run artifacts found — run repro.launch.dryrun)")
    except Exception as e:  # roofline needs dry-run artifacts
        print(f"  roofline skipped: {e}")

    print("\n== summary (name,value) ==")
    for k, v in out:
        print(f"{k},{v}")
    print(f"total_wall_s,{time.time() - t0:.0f}")
    return 0


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    sys.exit(main())
