"""Sweep launcher — the paper's headline workload as one command.

``python -m repro.launch.sweep --instances 48 --steps 1200`` reproduces the
paper's 6-node × 8-instance batch (at CPU-friendly horizons), with optional
failure injection and checkpointing:

``python -m repro.launch.sweep --instances 48 --fail-prob 0.1 --ckpt-dir /tmp/sw``

Device sharding (the paper's "across an arbitrary number of computing
nodes"): ``--devices N`` sizes the 1-D device mesh the instance axis is
sharded over. On a CPU host it also *simulates* N devices by setting
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` before jax
initializes — same code path as a real N-accelerator host. ``--workers W``
is the per-device instance granularity (the paper's instances-per-node),
so the fault injector models an ``N × W`` grid and the planner pads each
device block to a multiple of W:

``python -m repro.launch.sweep --devices 4 --workers 8 --instances 32``

``--pipeline`` (default on) overlaps host I/O — checkpoint writes, dataset
shard compression — with device compute by deferring chunk c's file I/O
until chunk c+1 has been dispatched; ``--no-pipeline`` forces the fully
synchronous loop (bit-for-bit identical output either way).

Scenario selection (the registry catalog, ``repro.core.scenarios``):

``python -m repro.launch.sweep --scenario lane_drop``
    every instance runs the lane-drop bottleneck;
``python -m repro.launch.sweep --scenario-mix highway_merge,stop_and_go``
    instances are assigned the listed scenarios round-robin;
``python -m repro.launch.sweep --scenario-mix all``
    round-robin over every registered scenario.

Mixed-sweep dispatch (``--dispatch``, default ``auto``): ``grouped`` repacks
instances per scenario into dense switch-free compiled calls each chunk
(~k× faster on a k-scenario mix; on a multi-device mesh the groups are
LPT-packed into per-device blocks instead); ``switch`` keeps the
single-compile vmapped ``lax.switch`` program; ``auto`` picks grouped
whenever the roster is mixed. All modes are bit-for-bit
trajectory-equivalent.

Phase-III dataset output (``--dataset-dir``): turns on trajectory recording
(``repro.core.record``) and streams every finished instance's time series +
token stream into npz/jsonl shards with a manifest
(``repro.data.shards.DatasetWriter``) — the ML-ready replacement for the
old single monolithic records JSON (``--out`` still writes the summary
digest):

``python -m repro.launch.sweep --scenario-mix all --dataset-dir /tmp/ds``

Unattended-run supervision (the paper's §5.2 completion contract,
``repro.core.fleet``): the loop is always the supervised one — failed
instances are charged against a per-instance retry budget
(``--max-retries``) with exponential re-queue backoff, poison instances
are quarantined instead of thrashing the fleet, and every event lands in
an append-only run journal (``--journal``, defaulting to
``<ckpt-dir>/journal.jsonl``). ``--hang-prob`` and ``--poison`` extend
the injected fault taxonomy beyond crashes; ``--chunk-deadline`` journals
wall-clock overruns; ``--heartbeat-file`` makes the worker emit atomic
liveness beacons for the process supervisor
(``python -m repro.launch.controller``), which SIGKILLs and resumes a
stalled worker:

``python -m repro.launch.sweep --fail-prob 0.1 --max-retries 3 \\
    --chunk-deadline 60 --ckpt-dir /tmp/sw``
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time


def _write_heartbeat(path: str, chunk: int, done: float) -> None:
    """Atomically publish a liveness beacon (tmp + rename, never torn).

    The process controller (``repro.launch.controller``) polls this file's
    payload; a stale ``time`` means the worker is hung and gets SIGKILLed.
    """
    tmp = f"{path}.tmp-{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump({"chunk": chunk, "done": done, "time": time.time()}, f)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)


def _preparse_devices(argv: list[str]) -> int | None:
    """Extract ``--devices N`` from argv WITHOUT importing jax.

    ``--xla_force_host_platform_device_count`` only works before the
    backend initializes, so the launcher must set it before the real
    argparse run (whose ``choices=list_scenarios()`` pulls in jax). Only
    the exact ``--devices``/``--devices=`` spellings match — the real
    parser runs with ``allow_abbrev=False`` so no other spelling is
    accepted there either — and malformed values are left for argparse to
    reject with a proper usage error.
    """
    for i, a in enumerate(argv):
        value = None
        if a == "--devices" and i + 1 < len(argv):
            value = argv[i + 1]
        elif a.startswith("--devices="):
            value = a.split("=", 1)[1]
        if value is not None:
            try:
                return int(value)
            except ValueError:
                return None  # argparse prints the clean error
    return None


def build_run(
    cfg,
    *,
    mesh,
    workers: int = 1,
    fail_prob: float = 0.0,
    hang_prob: float = 0.0,
    straggler_prob: float = 0.0,
    poison: tuple[int, ...] = (),
    max_retries: int = 3,
    ckpt_dir: str | None = None,
    journal_path: str | None = None,
    dataset_dir: str | None = None,
    shard_size: int = 16,
):
    """Build one supervised sweep: ``(runner, run_kwargs)``.

    ``runner`` is the :class:`~repro.core.sweep.SweepRunner` for ``cfg`` on
    ``mesh`` with ``workers`` instances per device; ``run_kwargs`` holds
    the ``faults``/``policy``/``ckpt``/``writer``/``journal`` arguments of
    :func:`repro.core.fleet.run_supervised`. The seeded fault schedule
    models the devices x workers grid, and the journal defaults to
    ``<ckpt_dir>/journal.jsonl`` so a rebuilt run resumes its fleet state.
    Calling this again with the same arguments is how a stopped sweep is
    resumed from its checkpoint directory.
    """
    from repro.ckpt import CheckpointManager
    from repro.core.fault import FaultModel
    from repro.core.fleet import RetryPolicy, RunJournal
    from repro.core.sweep import SweepRunner
    from repro.data.shards import DatasetWriter

    runner = SweepRunner(cfg, mesh=mesh, workers_per_device=workers)
    faults = FaultModel.random_model(
        n_workers=runner._n_workers(),
        n_chunks=max(cfg.steps_per_instance // cfg.chunk_steps * 3, 8),
        fail_prob=fail_prob,
        hang_prob=hang_prob,
        straggler_prob=straggler_prob,
        poison_instances=tuple(poison),
        seed=cfg.seed,
    )
    journal_path = journal_path or (
        os.path.join(ckpt_dir, "journal.jsonl") if ckpt_dir else None
    )
    return runner, {
        "faults": faults,
        "policy": RetryPolicy(max_retries=max_retries),
        "ckpt": CheckpointManager(ckpt_dir) if ckpt_dir else None,
        "writer": (
            DatasetWriter(dataset_dir, cfg, shard_size=shard_size)
            if dataset_dir else None
        ),
        "journal": RunJournal(journal_path) if journal_path else None,
    }


def main() -> None:
    devices = _preparse_devices(sys.argv[1:])
    if devices is not None and devices >= 1 and "jax" not in sys.modules:
        from repro.launch.mesh import force_host_device_count

        force_host_device_count(devices)

    # heavy imports AFTER the device-count flag is in place
    from repro.core.aggregate import aggregate_metrics, metrics_to_records
    from repro.core.fleet import format_completion_table, run_supervised
    from repro.core.neighbors import CHOICES
    from repro.core.record import RecordConfig
    from repro.core.scenario import SimConfig
    from repro.core.scenarios import list_scenarios
    from repro.core.sweep import SweepConfig
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.mesh import make_host_mesh

    enable_compile_cache()

    # allow_abbrev off: the --devices pre-parse above matches exact
    # spellings only, so abbreviations must not silently bypass it
    ap = argparse.ArgumentParser(allow_abbrev=False)
    ap.add_argument("--instances", type=int, default=48)
    ap.add_argument("--steps", type=int, default=1200)
    ap.add_argument("--chunk-steps", type=int, default=400)
    ap.add_argument("--slots", type=int, default=32)
    ap.add_argument("--scenario", default="highway_merge",
                    choices=list_scenarios(),
                    help="workload every instance runs (registry name)")
    ap.add_argument("--scenario-mix", default=None,
                    help="comma-separated scenario names assigned to "
                         "instances round-robin, or 'all' for the whole "
                         "registry (overrides --scenario)")
    ap.add_argument("--dispatch", default="auto",
                    choices=["auto", "switch", "grouped"],
                    help="mixed-sweep chunk dispatch: grouped = per-scenario "
                         "repacked compiled calls (no lax.switch tax), "
                         "switch = one vmapped-switch compile, auto = "
                         "grouped iff the scenario roster is mixed")
    ap.add_argument("--neighbor-impl", default="auto", choices=CHOICES,
                    help="neighborhood engine implementation; auto = dense "
                         "on a TPU up to 2048 slots, sort above that and on "
                         "CPU hosts")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--vary-horizon", action="store_true")
    ap.add_argument("--fail-prob", type=float, default=0.0,
                    help="per-worker per-chunk probability of an injected "
                         "crash (chunk progress lost, instances reverted "
                         "and re-queued)")
    ap.add_argument("--hang-prob", type=float, default=0.0,
                    help="per-worker per-chunk probability of an injected "
                         "hang (deadline timeout: same revert as a crash, "
                         "distinct journal event)")
    ap.add_argument("--straggler-prob", type=float, default=0.0,
                    help="per-worker per-chunk probability of a journaled "
                         "slow-but-successful chunk (results kept)")
    ap.add_argument("--poison", default="",
                    help="comma-separated logical instance ids that crash "
                         "every chunk they run — exhausts the retry budget "
                         "and exercises quarantine")
    ap.add_argument("--max-retries", type=int, default=3,
                    help="per-instance retry budget: an instance failing "
                         "more than this many times is quarantined "
                         "(excluded from scheduling and from the eligible "
                         "completion denominator)")
    ap.add_argument("--chunk-deadline", type=float, default=None,
                    help="wall-clock seconds per chunk before a 'deadline' "
                         "event is journaled (hard hangs are killed by the "
                         "controller's heartbeat timeout)")
    ap.add_argument("--heartbeat-file", default=None,
                    help="write an atomic {chunk, done, time} liveness "
                         "beacon here after every committed chunk (the "
                         "controller's hang detector)")
    ap.add_argument("--journal", default=None,
                    help="append-only jsonl run journal (default: "
                         "<ckpt-dir>/journal.jsonl when --ckpt-dir is set)")
    ap.add_argument("--devices", type=int, default=None,
                    help="device-mesh size the instance axis is sharded "
                         "over (default: all visible devices); on CPU "
                         "also forces that many simulated host devices")
    ap.add_argument("--workers", type=int, default=1,
                    help="instances per device (the paper's per-node "
                         "parallelism): failure injection models a "
                         "devices x workers grid and device blocks are "
                         "padded to a multiple of this")
    ap.add_argument("--pipeline", dest="pipeline", action="store_true",
                    default=True,
                    help="overlap host I/O (checkpoints, dataset shards) "
                         "with device compute (default)")
    ap.add_argument("--no-pipeline", dest="pipeline", action="store_false",
                    help="fully synchronous chunk loop (same bits, "
                         "no overlap)")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--out", default=None, help="write records JSON here")
    ap.add_argument("--dataset-dir", default=None,
                    help="stream a sharded Phase-III dataset here "
                         "(npz/jsonl shards + manifest); implies recording")
    ap.add_argument("--record-every", type=int, default=0,
                    help="trajectory recording stride in steps (0 = off; "
                         "--dataset-dir defaults it to 10)")
    ap.add_argument("--record-slots", type=int, default=8,
                    help="vehicle slots recorded for token streams")
    ap.add_argument("--shard-size", type=int, default=16,
                    help="instances per dataset shard")
    args = ap.parse_args()
    if args.workers < 1:
        ap.error("--workers must be >= 1 (instances per device)")
    if args.devices is not None and args.devices < 1:
        ap.error("--devices must be >= 1")

    record_every = args.record_every
    if args.dataset_dir and record_every == 0:
        record_every = 10
    record = (
        RecordConfig(record_every=record_every, k_slots=args.record_slots)
        if record_every > 0
        else None
    )

    if args.scenario_mix:
        mix = (
            tuple(list_scenarios())
            if args.scenario_mix.strip() == "all"
            else tuple(s.strip() for s in args.scenario_mix.split(",") if s.strip())
        )
    else:
        mix = ()

    cfg = SweepConfig(
        n_instances=args.instances,
        steps_per_instance=args.steps,
        chunk_steps=args.chunk_steps,
        sim=SimConfig(n_slots=args.slots, neighbor_impl=args.neighbor_impl,
                      scenario=args.scenario),
        seed=args.seed,
        vary_horizon=args.vary_horizon,
        scenario_mix=mix,
        dispatch=args.dispatch,
        record=record,
    )
    # the mesh is the source of truth for device count; --workers adds the
    # per-device instance granularity, and the injector models the full
    # devices x workers worker grid (the paper's nodes x instances-per-node)
    try:
        poison = tuple(
            int(p) for p in args.poison.split(",") if p.strip()
        )
    except ValueError:
        ap.error("--poison takes comma-separated integer instance ids")
    mesh = make_host_mesh(max_workers=args.devices)
    runner, run_kw = build_run(
        cfg, mesh=mesh, workers=args.workers, fail_prob=args.fail_prob,
        hang_prob=args.hang_prob, straggler_prob=args.straggler_prob,
        poison=poison, max_retries=args.max_retries,
        ckpt_dir=args.ckpt_dir, journal_path=args.journal,
        dataset_dir=args.dataset_dir, shard_size=args.shard_size,
    )
    writer = run_kw["writer"]
    n_devices = int(mesh.devices.size)

    print(f"[sweep] scenarios: {', '.join(cfg.scenarios)} "
          f"({'mixed round-robin' if len(cfg.scenarios) > 1 else 'uniform'}) "
          f"| dispatch {cfg.effective_dispatch} "
          f"| {n_devices} device(s) x {args.workers} worker(s) "
          f"| {'pipelined' if args.pipeline else 'synchronous'} I/O"
          + (f" | recording every {record_every} steps" if record else ""))
    def on_progress(c: int, done: float) -> None:
        print(f"[sweep] chunk {c}: {done*100:.1f}% complete")
        if args.heartbeat_file:
            _write_heartbeat(args.heartbeat_file, c, done)

    t0 = time.perf_counter()
    state, info = run_supervised(
        runner, **run_kw, pipeline=args.pipeline,
        chunk_deadline=args.chunk_deadline, on_progress=on_progress,
    )
    dt = time.perf_counter() - t0
    summary = aggregate_metrics(
        state.metrics, scenario_ids=state.scenario_id,
        scenario_names=cfg.scenarios,
    )
    print(f"[sweep] done in {dt:.1f}s — completion "
          f"{info['completion_rate']*100:.0f}% "
          f"(eligible {info['eligible_completion_rate']*100:.0f}%), "
          f"{info['chunks_run']} chunks, "
          f"{len(info['failure_events'])} failure events, "
          f"{len(info['quarantined'])} quarantined")
    print(format_completion_table(info["report"]))
    print(f"[sweep] {json.dumps(summary, indent=1)}")
    if writer is not None:
        manifest = writer.finalize(summary=summary, fault_info=info)
        print(f"[sweep] wrote sharded dataset: {manifest} "
              f"({len(writer.written)} instances)")
    if args.out:
        records = metrics_to_records(
            state.metrics, state.params,
            scenario_ids=state.scenario_id, scenario_names=cfg.scenarios,
        )
        with open(args.out, "w") as f:
            json.dump({"summary": summary, "records": records,
                       "fault_info": info}, f, indent=1)
        print(f"[sweep] wrote dataset to {args.out}")


if __name__ == "__main__":
    main()
