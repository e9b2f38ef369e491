#!/usr/bin/env python3
"""Compile a four-chip cell's block programs for a described v5e:2x2,
without the chip, before any four-chip call.

    JAX_PLATFORMS=cpu python bench/compile_x4.py [--config mix64_x4]

Builds the runner the harness builds for a configuration
(``bench/configs/<config>.json``), on a mesh of the four described
devices, and compiles both ``shard_map`` block executors (uniform blocks,
and the mixed-block fallback) at the first chunk's block shape (every
instance live: ``cap`` = instances / chips rows per device). Prints each
program's memory analysis. A compile that passes here is not a chip run.
"""

from __future__ import annotations

import argparse
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", default="mix64_x4")
    args = ap.parse_args(argv)

    import json

    import run

    with open(os.path.join(BENCH, "configs", args.config + ".json")) as f:
        cfg = json.load(f)
    sys.path.insert(0, os.path.join(run.ROOT, "src"))
    import jax
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from repro.core.sweep import SweepRunner

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chips = cfg["devices"]["chips"]
    mesh = Mesh(np.asarray(topo.devices[:chips]), ("workers",))
    scfg = run.sweep_config(cfg, 0)
    runner = SweepRunner(scfg, mesh=mesh,
                         workers_per_device=cfg["devices"]["workers_per_chip"])
    shapes = jax.eval_shape(SweepRunner(scfg).init)
    rows = scfg.n_instances          # D * cap with every instance live
    spec = NamedSharding(mesh, P("workers"))

    def arg(x):
        return jax.ShapeDtypeStruct((rows,) + x.shape[1:], x.dtype,
                                    sharding=spec)

    sub = jax.tree.map(arg, (shapes.sim, shapes.metrics, shapes.params,
                             shapes.horizon, shapes.trace))
    row_sid = jax.ShapeDtypeStruct((rows,), np.int32, sharding=spec)
    bsid = jax.ShapeDtypeStruct((chips,), np.int32, sharding=spec)
    for name in ("_block_fn_uniform", "_block_fn_full"):
        fn = getattr(runner, name)
        compiled = fn.lower(*sub, row_sid, bsid).compile()
        print(f"{name}: compiled for {chips} x {topo.devices[0].device_kind}"
              f" at {rows} rows; {compiled.memory_analysis()}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
