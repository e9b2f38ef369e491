#!/usr/bin/env python3
"""The control of the ``correct`` comparison: the plain reference computed
one precision below what the configuration states (bfloat16 for float32),
put in the program's place, and compared with the float32 reference by the
same numbers and limits. It has to come out not correct.

    python bench/control.py --workload merge256.slice --seeds 1,2,3

It runs at the cell's own size: the cell's sample of instances, stepped to
the step count a window reaches (``slice``: the fill and ``--chunks`` more
chunks; ``sweeps``: each instance's horizon). Prints one JSON line per
seed with every number beside its limit, and exits 1 if any seed's control
passes.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)

import check  # noqa: E402
import reference  # noqa: E402
import run  # noqa: E402


def control(workload: str, seed: int, chunks: int = 3,
            overrides: dict | None = None) -> dict:
    import jax.numpy as jnp

    cell, _, _, cfg, traffic = run.load_cell(workload, overrides)
    sw = cfg["sweep"]
    n, rng = sw["n_instances"], np.random.default_rng(seed)
    sseed = traffic["sweep_seed"]
    if traffic["style"] == "slice":
        fill = int(-(-traffic["fill_sim_seconds"]
                     // (sw["chunk_steps"] * cfg["sim"]["dt"])))
        ids = check.sample(n, traffic["sample"], rng)
        target = (fill + chunks) * sw["chunk_steps"]
    else:
        ids = check.sample(n, traffic["sample"], rng)
        target = 10**9
    targets = [target] * len(ids)
    exact = reference.rollout(cfg, sseed, ids, targets)
    low = reference.rollout(cfg, sseed, ids, targets, dtype=jnp.bfloat16)
    numbers = check.compare(run.reference_view(low, as_program=True),
                            run.reference_view(exact))
    if traffic["style"] == "sweeps":
        numbers["incomplete"] = 0
    ok, checks = check.judge(numbers, cfg["limits"])
    return {"workload": workload, "seed": seed, "correct": ok,
            "checks": checks}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--chunks", type=int, default=3)
    args = ap.parse_args(argv)
    passed = 0
    for s in args.seeds.split(","):
        r = control(args.workload, int(s), args.chunks)
        passed += r["correct"]
        print(json.dumps(r), flush=True)
    return 1 if passed else 0


if __name__ == "__main__":
    sys.exit(main())
