"""Paper Table 5.1 / Figure 5.1 — sample simulation throughput.

Three layers of validation:
1. **Schedule accounting** — reproduce the paper's exact numbers: 48·t
   completed runs per 15-minute slice on the cluster vs a 9.73-min/run
   personal computer, 2304 vs 74 after 12 h (31× speedup).
2. **Measured vectorization** — on this host, one simulation instance vs a
   48-wide vmapped batch (the per-node 8× of the paper collapses into the
   batch axis on an accelerator): veh-steps/s and the batch-over-serial
   speedup, plus the projected 12-hour run count for the paper's cluster.
3. **Neighbour engines** — per-step time of each engine against the slot
   count, 48 instances at once; on a TPU this is the table that decides
   what ``neighbor_impl="auto"`` picks there.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.common import emit, timeit
from repro.core.metrics import (
    PAPER_CLUSTER,
    PAPER_PC,
    PAPER_TIMESTAMPS,
    ClusterSpec,
    cluster_timeline,
    personal_timeline,
    speedup_at,
)
from repro.core.scenario import SimConfig, sample_scenario_params
from repro.core.simulator import SimMetrics, init_state, rollout, rollout_chunk

STEPS = 600
N_BATCH = 48
ENGINE_STEPS = 20
ENGINE_SLOTS = (48, 128, 512)
ENGINE_SLOTS_TPU = (64, 128, 256, 512, 1024, 2048)


def run() -> None:
    # ---- 1. schedule accounting vs the paper's published numbers --------
    spec = ClusterSpec()  # 6 nodes x 8 instances, 15-min walltime
    cluster = cluster_timeline(spec, PAPER_TIMESTAMPS)
    pc = personal_timeline(720 / 74, PAPER_TIMESTAMPS)
    match_cluster = cluster == PAPER_CLUSTER
    # the paper's PC column is an empirical (slightly non-uniform) rate;
    # the constant-rate model must track within ±3 and hit 74 at 12 h
    track_pc = (
        all(abs(a - b) <= 3 for a, b in zip(pc, PAPER_PC)) and pc[-1] == 74
    )
    speedup = speedup_at(spec, 720 / 74, 720.0)
    emit(
        "table5.1_schedule_accounting", 0.0,
        f"cluster_timeline_match={match_cluster} pc_tracks±3={track_pc} "
        f"speedup_12h={speedup:.1f}x (paper: ~31x)",
    )

    # ---- 2. measured single vs vmapped-batch throughput -----------------
    cfg = SimConfig(n_slots=48)

    def one(i):
        k = jax.random.fold_in(jax.random.key(0), i)
        sp = sample_scenario_params(jax.random.fold_in(k, 1), cfg)
        return rollout(k, cfg, sp, STEPS)

    single = jax.jit(lambda: one(0))
    batched = jax.jit(lambda: jax.vmap(one)(jnp.arange(N_BATCH)))

    t1 = timeit(lambda: single())
    tn = timeit(lambda: batched())
    per_instance_serial = t1
    per_instance_batched = tn / N_BATCH
    speedup = per_instance_serial / per_instance_batched
    sim_seconds = STEPS * cfg.dt
    emit(
        "fig5.1_single_instance", t1 * 1e6,
        f"sim_rate={sim_seconds/t1:.1f}x_realtime",
    )
    emit(
        "fig5.1_vmapped_batch48", tn * 1e6,
        f"per_instance={per_instance_batched*1e6:.0f}us "
        f"vectorization_speedup={speedup:.1f}x "
        f"runs_per_12h_this_host={int(12*3600/ (tn / N_BATCH)):,}",
    )

    # ---- 3. neighborhood engine: per-implementation step time ---------
    # "reference" is the per-query O(N²) scan family the seed used; dense /
    # sort / pallas are the fused engine paths (repro.core.neighbors). Each
    # row times one vmapped ENGINE_STEPS-step rollout_chunk of N_BATCH
    # highway_merge instances with the engine pinned. On a TPU the slot
    # counts are the chip table behind neighbors.TPU_DENSE_MAX_SLOTS.
    on_tpu = jax.default_backend() == "tpu"
    impls = ["reference", "dense", "sort"]
    if on_tpu:
        impls.append("pallas")   # interpret mode off-TPU is not a timing
    keys = jax.random.split(jax.random.key(1), N_BATCH)
    horizon = jnp.full((N_BATCH,), 1 << 30, jnp.int32)
    for n_slots in ENGINE_SLOTS_TPU if on_tpu else ENGINE_SLOTS:
        base = None
        for impl in impls:
            icfg = SimConfig(n_slots=n_slots, neighbor_impl=impl)
            sp = jax.vmap(lambda k: sample_scenario_params(k, icfg))(keys)
            st = jax.vmap(lambda k: init_state(icfg, k))(keys)
            met = jax.vmap(lambda _: SimMetrics.zeros())(keys)
            chunk = jax.jit(jax.vmap(
                lambda s, m, p, h, icfg=icfg: rollout_chunk(
                    s, m, p, h, icfg, ENGINE_STEPS)))
            t = timeit(chunk, st, met, sp, horizon)
            base = t if base is None else base
            emit(
                f"neighbor_{impl}_slots{n_slots}", t * 1e6,
                f"{1e3 * t / ENGINE_STEPS:.3f}_ms_per_step "
                f"{ENGINE_STEPS * N_BATCH * n_slots / t:.0f}_veh_steps_per_s "
                f"speedup_vs_reference={base/t:.2f}x",
            )
