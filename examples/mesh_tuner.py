"""Mesh-Tuner (beyond the paper): SoC-Tuner's IMOO loop pointed at OUR OWN
distributed-training configuration.

The analogy is exact: the paper explores SoC parameters against an expensive
VLSI flow; here the "design point" is a (microbatch, remat, sharding-rule)
configuration, the "flow" is a 256-chip dry-run compile (tens of seconds —
genuinely expensive), and the metrics are the three roofline terms
(compute/memory/collective seconds) from the compiled HLO. The same GP +
information-gain acquisition drives the search — no code changes to the
core.

    PYTHONPATH=src python examples/mesh_tuner.py --arch qwen3-14b \
        --shape train_4k --T 5 --b 3
"""
import argparse
import itertools
import json
import subprocess
import sys
import tempfile
import os

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))  # benchmarks

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import fit_gp, imoo_scores, pareto_mask

# ---------------------------------------------------------- design space
KNOBS = {
    "microbatch": [1, 2, 4, 8],
    "remat": [True, False],
    "fsdp": ["both", "data", "off"],     # embed_fsdp candidate axes
    "zero1": [True, False],              # opt-state data sharding
}


def knob_grid():
    keys = list(KNOBS)
    for combo in itertools.product(*(KNOBS[k] for k in keys)):
        yield dict(zip(keys, combo))


def encode(pt: dict) -> list[float]:
    return [np.log2(pt["microbatch"]) / 3.0, float(pt["remat"]),
            {"both": 1.0, "data": 0.5, "off": 0.0}[pt["fsdp"]],
            float(pt["zero1"])]


def to_overrides(pt: dict) -> dict:
    rules = {}
    if pt["fsdp"] == "off":
        rules["embed_fsdp"] = []
    elif pt["fsdp"] == "data":
        rules["embed_fsdp"] = [["data"]]
    ov = {"microbatch": pt["microbatch"], "remat": pt["remat"]}
    if rules:
        ov["rules"] = rules
    if not pt["zero1"]:
        ov["zero1"] = False
    return ov


# ------------------------------------------------------------ evaluation
def evaluate(arch: str, shape: str, mesh: str, pt: dict, out_dir: str) -> dict:
    """One dry-run compile in a subprocess (needs its own 512-dev runtime)."""
    cmd = [sys.executable, "-m", "repro.launch.dryrun", "--arch", arch,
           "--shape", shape, "--mesh", mesh, "--out", out_dir,
           "--overrides", json.dumps(to_overrides(pt))]
    # the dry run compiles for 512 faked host devices; this process may
    # already hold an accelerator, so the child stays on the CPU
    env = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu")
    res = subprocess.run(cmd, capture_output=True, text=True, env=env)
    line = res.stdout.strip().splitlines()[-1] if res.stdout.strip() else "{}"
    rec = json.loads(line)
    if rec.get("status") != "ok":
        raise RuntimeError(rec.get("error", "compile failed"))
    from benchmarks.roofline import terms
    t = terms(rec)
    return {"compute_s": t["compute_s"], "memory_s": t["memory_s"],
            "collective_s": t["collective_s"],
            "step_s": max(t["compute_s"], t["memory_s"], t["collective_s"]),
            "mem_bytes": rec.get("temp_size_in_bytes", 0),
            "roofline_frac": t["roofline_frac"]}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-14b")
    ap.add_argument("--shape", default="train_4k")
    ap.add_argument("--mesh", default="single")
    ap.add_argument("--T", type=int, default=5, help="BO rounds")
    ap.add_argument("--b", type=int, default=3, help="init points")
    args = ap.parse_args()

    pool = list(knob_grid())
    X = jnp.asarray([encode(p) for p in pool], jnp.float32)
    rng = np.random.default_rng(0)
    evaluated: dict[int, dict] = {}
    tmp = tempfile.mkdtemp(prefix="meshtuner_")

    def run_row(i: int):
        pt = pool[i]
        try:
            m = evaluate(args.arch, args.shape, args.mesh, pt, tmp)
        except RuntimeError as e:
            m = {"step_s": 1e6, "collective_s": 1e6, "mem_bytes": 1e15,
                 "roofline_frac": 0.0}
            print(f"  x {pt} -> compile FAILED ({e})")
            return m
        print(f"  . {pt} -> step={m['step_s']:.2f}s "
              f"coll={m['collective_s']:.2f}s "
              f"roofline={m['roofline_frac']*100:.1f}%")
        return m

    print(f"== Mesh-Tuner: {args.arch} / {args.shape} on {args.mesh} mesh "
          f"({len(pool)} candidate configs) ==")
    for i in rng.choice(len(pool), size=args.b, replace=False):
        evaluated[int(i)] = run_row(int(i))

    for t in range(args.T):
        rows = sorted(evaluated)
        # objectives: minimize (step_s, collective_s, mem_bytes)
        Y = np.asarray([[evaluated[r]["step_s"], evaluated[r]["collective_s"],
                         evaluated[r]["mem_bytes"] / 1e9] for r in rows])
        state = fit_gp(X[np.asarray(rows)], jnp.asarray(-Y, jnp.float32),
                       steps=80)
        scores = np.array(imoo_scores(state, X, jax.random.PRNGKey(t), s=8))
        scores[np.asarray(rows)] = -np.inf
        nxt = int(np.argmax(scores))
        evaluated[nxt] = run_row(nxt)

    rows = sorted(evaluated)
    Y = np.asarray([[evaluated[r]["step_s"], evaluated[r]["collective_s"],
                     evaluated[r]["mem_bytes"] / 1e9] for r in rows])
    mask = np.asarray(pareto_mask(jnp.asarray(Y)))
    print("\nPareto-optimal configurations:")
    for r, keep in zip(rows, mask):
        if keep:
            print(f"  {pool[r]} -> step={Y[rows.index(r), 0]:.2f}s "
                  f"mem={Y[rows.index(r), 2]:.1f}GB "
                  f"roofline={evaluated[r]['roofline_frac']*100:.1f}%")
    best = max(evaluated, key=lambda r: evaluated[r]["roofline_frac"])
    print(f"\nBest roofline fraction: {pool[best]} "
          f"({evaluated[best]['roofline_frac']*100:.1f}%)")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
