#!/usr/bin/env python3
"""CPU rehearsal of the whole harness, before any chip call.

    JAX_PLATFORMS=cpu python bench/rehearse.py [--fault NAME] [--cells a,b]

Runs every cell of ``BENCHMARK.json`` end to end at a tiny size
(``TINY``), with the harness's look for a chip skipped, on four virtual
CPU devices so the four-chip cell's sharded path runs too. Only paths and
control flow are checked here: every number it prints is a CPU number and
none is a device metric. ``--fault`` plants one of ``tests/faults.py``
under the timed path (the run must then come out not correct). Also checks
the trace reduction on the small synthetic traces of ``tests``.
Prints one JSON line per cell; exits 1 when a sound run is not correct or
a planted fault is.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=4").strip()

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(BENCH, "tests"))

TINY = {"sweep": {"n_instances": 16, "steps_per_instance": 300},
        "sim": {"n_slots": 24}, "devices": {"workers_per_chip": 2},
        "traffic": {"fill_sim_seconds": 20, "sample": 4}}


def tiny(cell_name: str) -> dict:
    import run

    cell, _, _, cfg, traffic = run.load_cell(cell_name)
    out = json.loads(json.dumps(TINY))
    if traffic["style"] == "slice":
        out["sweep"]["steps_per_instance"] = cfg["sweep"]["steps_per_instance"]
    return out


def main(argv=None) -> int:
    import run
    import faults

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--fault", default=None, choices=sorted(faults.FAULTS))
    ap.add_argument("--cells", default=None)
    ap.add_argument("--seed", type=int, default=2**31 + 11)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--trace", type=int, default=0)
    args = ap.parse_args(argv)
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        cells = [w["name"] for w in json.load(f)["workloads"]]
    if args.cells:
        cells = args.cells.split(",")
    bad = 0
    if args.fault is None:
        import test_bench

        test_bench.test_reduction_matches_a_brute_force_count()
        test_bench.test_reduction_attributes_gaps_to_innermost_span()
        test_bench.test_reduction_finds_nothing_without_device_ops()
        print(json.dumps({"trace_reduction": "ok"}), flush=True)
    for name in cells:
        plant = faults.FAULTS[args.fault] if args.fault else None
        r = run.run_cell(name, args.seed, args.seconds, bool(args.trace),
                         allow_cpu=True, overrides=tiny(name), plant=plant)
        want = args.fault is None
        bad += r["correct"] != want
        print(json.dumps({"cell": name, "fault": args.fault, **r}),
              flush=True)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
