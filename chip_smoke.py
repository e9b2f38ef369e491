#!/usr/bin/env python3
"""Smoke run of the supervised sweep on a TPU — the quickest proof that the
system still starts on the chip.

    python chip_smoke.py              # one chip (the default phase)
    python chip_smoke.py --four-chip  # a four-chip host: sharded vs one chip

Everything runs in this one process (a chip belongs to one process at a
time), through the objects the sweep launcher ``python -m repro.launch.sweep``
builds: ``SweepRunner`` -> ``run_supervised`` with a ``CheckpointManager``,
a ``RunJournal``, a seeded ``FaultModel`` and a ``DatasetWriter``
(``repro.launch.sweep.build_run``).

The default phase runs the paper's sweep at the deployment's width: 256
vehicle slots (about 85 veh/km/lane on the default 3-lane 1 km road), the
four-scenario mix on 8 workers, 1200 steps in 400-step chunks with varied
horizons, recording every 10 steps into shards, a checkpoint every chunk
and injected crashes at ``fail_prob=0.1``, on the default neighbour engine.
The deployment runs 2048 instances; the smoke cuts that to 128 (never the
slots) so that all three steps fit in 1200 s on one v5e chip, and prints
the cut. Its steps:

1. the supervised sweep reaches eligible completion 1.0 with a valid
   manifest and shards that ``ShardedDataset`` reads back;
2. a second run stopped after one chunk and resumed by a fresh
   ``SweepRunner`` from its checkpoint directory equals the uninterrupted
   run: summary and shard contents, bit for bit;
3. a 64-instance slice of the same sweep gives identical summaries and
   records with every neighbour engine (run side by side in threads, so
   that compiles overlap device time), and the ``pallas`` chunk program
   holds a Mosaic kernel (``tpu_custom_call``), so interpret mode cannot
   pass unnoticed.

``--four-chip`` runs only the sharded path and what it is compared with: the
same sweep, cut to 32 instances (8 per chip) and run on the ``dense``
engine, on a four-device mesh and on one chip, which must give identical
summaries and shards, with the resting state spread over all four devices
and memory in use on each chip.

The last line of stdout is ``{"ok": true, "device": {...}}``. Without a TPU
the script exits non-zero and prints no such line. Times printed along the
way are smoke readings, not benchmarks.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(REPO, "src"))

DEPLOYMENT_INSTANCES = 2048
INSTANCES = 128         # the cut that fits the smoke's time (never slots)
FOUR_CHIP_INSTANCES = 32
SLOTS = 256
STEPS = 1200
CHUNK = 400
WORKERS = 8             # instances per device (the paper's per-node count)
FAIL_PROB = 0.1
RECORD_EVERY = 10
K_SLOTS = 8
SHARD_SIZE = 64
PARITY_INSTANCES = 64
ENGINES = ("reference", "dense", "sort", "pallas")


class SmokeFailure(Exception):
    """A phase produced a wrong result."""


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


_T0 = time.perf_counter()


def log(msg: str) -> None:
    print(f"[smoke +{time.perf_counter() - _T0:.0f}s] {msg}", flush=True)


def log_cut(n_instances: int) -> None:
    log(f"instances cut from the deployment's {DEPLOYMENT_INSTANCES} to "
        f"{n_instances} to fit the smoke's time; slots stay {SLOTS}")


class CompileCount:
    """Programs built while the context is open: each is compiled or
    loaded from the persistent cache (``jax.monitoring`` events)."""

    def __enter__(self) -> "CompileCount":
        import jax

        self.secs: list[float] = []
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)
        return self

    def __exit__(self, *exc) -> None:
        import jax

        jax.monitoring.unregister_event_duration_listener(self._duration)
        jax.monitoring.unregister_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.secs.append(secs)

    def _event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def __str__(self) -> str:
        return (f"{len(self.secs)} programs built ({self.hits} loaded from "
                f"the persistent cache), {sum(self.secs):.1f}s in all, "
                f"largest {max(self.secs, default=0.0):.1f}s")


def sweep_config(n_instances: int, seed: int,
                 neighbor_impl: str | None = None):
    """The smoke sweep: the launcher's ``--scenario-mix all --vary-horizon
    --dataset-dir`` run at ``SLOTS`` slots, on the default neighbour engine
    unless ``neighbor_impl`` names another."""
    from repro.core.record import RecordConfig
    from repro.core.scenario import SimConfig
    from repro.core.scenarios import list_scenarios
    from repro.core.sweep import SweepConfig

    return SweepConfig(
        n_instances=n_instances,
        steps_per_instance=STEPS,
        chunk_steps=CHUNK,
        sim=SimConfig(n_slots=SLOTS, neighbor_impl=(
            neighbor_impl or SimConfig.neighbor_impl)),
        seed=seed,
        vary_horizon=True,
        scenario_mix=tuple(list_scenarios()),
        record=RecordConfig(record_every=RECORD_EVERY, k_slots=K_SLOTS),
    )


def supervised(cfg, mesh, workers, root=None, *, max_chunks=10_000,
               finalize=True, quiet=False, after_first_chunk=None):
    """One sweep as the launcher runs it; ``root`` holds its checkpoints,
    journal and dataset. ``after_first_chunk()`` is called once the first
    chunk has been dispatched, which is when its programs have compiled.
    Returns ``(runner, state, info, summary)``."""
    from repro.core.aggregate import aggregate_metrics
    from repro.core.fleet import run_supervised
    from repro.launch.sweep import build_run

    runner, kw = build_run(
        cfg, mesh=mesh, workers=workers, fail_prob=FAIL_PROB,
        ckpt_dir=os.path.join(root, "ckpt") if root else None,
        dataset_dir=os.path.join(root, "dataset") if root else None,
        shard_size=SHARD_SIZE,
    )
    if after_first_chunk is not None:
        def first_chunk(state, hold=None):
            del runner.run_chunk            # back to the class's method
            out = runner.run_chunk(state, hold=hold)
            after_first_chunk()
            return out

        runner.run_chunk = first_chunk
    t0 = time.perf_counter()

    def progress(c: int, done: float) -> None:
        if not quiet:
            log(f"  chunk {c}: {done:.1%} complete "
                f"({time.perf_counter() - t0:.1f}s)")

    state, info = run_supervised(runner, **kw, pipeline=True,
                                 max_chunks=max_chunks, on_progress=progress)
    if kw["ckpt"] is not None:
        kw["ckpt"].wait()
    summary = aggregate_metrics(state.metrics, scenario_ids=state.scenario_id,
                                scenario_names=cfg.scenarios)
    if finalize and kw["writer"] is not None:
        kw["writer"].finalize(summary=summary, fault_info=info)
    return runner, state, info, summary


def same_json(a, b) -> bool:
    """Exact equality of JSON-able results (NaN-safe: compared as text)."""
    return json.dumps(a, sort_keys=True) == json.dumps(b, sort_keys=True)


def _neq(x: np.ndarray, y: np.ndarray) -> np.ndarray:
    x, y = np.asarray(x), np.asarray(y)
    if np.issubdtype(x.dtype, np.floating):
        return (x != y) & ~(np.isnan(x) & np.isnan(y))
    return x != y


def divergence(a, b) -> str:
    """Where two sweep states part: differing metric fields and the first
    recorded step whose trajectory rows differ."""
    import jax

    parts = []
    for name, x, y in zip(a.metrics._fields, a.metrics, b.metrics):
        bad = _neq(jax.device_get(x), jax.device_get(y))
        if bad.any():
            parts.append(f"{name}: {int(bad.sum())} instances")
    if a.trace is not None:
        bad = _neq(jax.device_get(a.trace.series),
                   jax.device_get(b.trace.series))
        rows = bad.any(axis=2)                              # [N, R]
        if rows.any():
            first = int(np.argmax(rows.any(axis=0)))
            fields = np.flatnonzero(bad[:, first].any(axis=0)).tolist()
            parts.append(
                f"trace: {int(rows.any(axis=1).sum())} instances, first at "
                f"step {(first + 1) * RECORD_EVERY} (channels {fields})"
            )
    return "; ".join(parts) or "states equal"


def shard_contents(root: str) -> dict:
    """Every shard's arrays and every records file's bytes. (The npz zip
    container also stamps its members' write time, so it is compared by
    content.)"""
    out: dict = {}
    for name in sorted(os.listdir(root)):
        path = os.path.join(root, name)
        if name.startswith("shard_") and name.endswith(".npz"):
            with np.load(path, allow_pickle=False) as z:
                out[name] = {k: z[k] for k in z.files}
        elif name.startswith("records_") and name.endswith(".jsonl"):
            with open(path, "rb") as f:
                out[name] = f.read()
    return out


def same_shards(a: dict, b: dict) -> bool:
    if sorted(a) != sorted(b):
        return False
    for name, x in a.items():
        y = b[name]
        if isinstance(x, bytes):
            if x != y:
                return False
            continue
        if sorted(x) != sorted(y):
            return False
        for k in x:
            if (x[k].dtype != y[k].dtype or x[k].shape != y[k].shape
                    or x[k].tobytes() != y[k].tobytes()):
                return False
    return True


def check_dataset(root: str, state, info) -> int:
    """Manifest valid and shards readable; returns the instances written."""
    import jax
    from repro.data.shards import FORMAT, ShardedDataset

    ds = ShardedDataset.load(root)
    m = ds.manifest
    check(m["format"] == FORMAT, f"manifest format {m['format']!r}")
    done = np.flatnonzero(np.asarray(jax.device_get(state.done))).tolist()
    ids = [i for s in m["shards"] for i in s["instances"]]
    check(sorted(ids) == done and len(set(ids)) == len(ids),
          "manifest shard index does not list every finished instance once")
    check(ds.n_instances == len(done), "n_instances_written mismatch")
    _, series, valid = ds.series()
    check(series.shape[0] == len(done), "series rows != instances written")
    check(bool(np.isfinite(series).all()), "non-finite recorded series")
    check(bool((valid > 0).all()), "an instance has no recorded rows")
    _, lengths = ds.token_streams()
    check(bool((lengths > 0).all()), "an empty token stream")
    check(len(ds.records()) == len(done), "records count mismatch")
    return len(done)


def phase_sweep(args, work, mesh):
    """Step 1: the supervised sweep at the deployment's width."""
    import jax
    from repro.core.fleet import format_completion_table
    from repro.core.neighbors import resolve_impl

    root = os.path.join(work, "sweep")
    cfg = sweep_config(INSTANCES, args.seed)
    first_chunk: list[float] = []
    log(f"sweep: {cfg.n_instances} instances x {SLOTS} slots, "
        f"{len(cfg.scenarios)} scenarios, {WORKERS} workers, {STEPS} steps "
        f"in {CHUNK}-step chunks, fail_prob {FAIL_PROB}, seed {cfg.seed}, "
        f"{resolve_impl(cfg.sim.neighbor_impl, SLOTS, jax.default_backend())}"
        " engine")
    t0 = time.perf_counter()
    with CompileCount() as built:
        _, state, info, summary = supervised(
            cfg, mesh, WORKERS, root,
            after_first_chunk=lambda: first_chunk.append(sum(built.secs)),
        )
    wall = time.perf_counter() - t0
    log("completion:\n" + format_completion_table(info["report"]))
    check(info["eligible_completion_rate"] == 1.0,
          f"eligible completion {info['eligible_completion_rate']}")
    n_written = check_dataset(os.path.join(root, "dataset"), state, info)
    veh_steps = float(np.sum(jax.device_get(state.metrics.speed_count)))
    stats = jax.devices()[0].memory_stats() or {}
    log(f"sweep: {wall:.1f}s wall, {info['chunks_run']} chunks, "
        f"{len(info['failure_events'])} failure events, "
        f"{len(info['quarantined'])} quarantined, {n_written} instances "
        f"in shards")
    log(f"compile: {first_chunk[0]:.1f}s in the first chunk; {built}")
    log(f"peak_bytes_in_use: {stats.get('peak_bytes_in_use', 'n/a')}")
    log(f"smoke reading, not a benchmark: {veh_steps / wall:,.0f} live "
        f"vehicle-steps/s over the whole sweep, compiles included")
    return summary, root


def phase_resume(args, work, mesh, ref_summary, ref_root):
    """Step 2: stop after one chunk, resume with a fresh SweepRunner."""
    root = os.path.join(work, "resume")
    cfg = sweep_config(INSTANCES, args.seed)
    t0 = time.perf_counter()
    with CompileCount() as built:
        _, state, info, _ = supervised(cfg, mesh, WORKERS, root, max_chunks=1,
                                       finalize=False, quiet=True)
        check(info["chunks_run"] == 1, "the stopped run did not stop")
        _, state, info, summary = supervised(cfg, mesh, WORKERS, root,
                                             quiet=True)
    check(info["eligible_completion_rate"] == 1.0,
          f"resumed eligible completion {info['eligible_completion_rate']}")
    check(same_json(summary, ref_summary),
          "resumed summary differs from the uninterrupted run")
    check(same_shards(shard_contents(os.path.join(root, "dataset")),
                      shard_contents(os.path.join(ref_root, "dataset"))),
          "resumed shards differ from the uninterrupted run")
    log(f"resume: stopped after 1 chunk, resumed to completion in "
        f"{info['chunks_run']} more; summary and shards equal the "
        f"uninterrupted run ({time.perf_counter() - t0:.1f}s; {built})")


def pallas_chunk_text(runner, state) -> str:
    """Compiled HLO of the ``pallas`` run's first per-scenario chunk
    program, at the shape of one scenario group."""
    import jax

    sids = np.asarray(jax.device_get(state.scenario_id))
    take = np.flatnonzero(sids == 0)
    sub = jax.tree.map(
        lambda x: x[take],
        (state.sim, state.metrics, state.params, state.horizon, state.trace),
    )
    return runner._roster_fns[0].lower(*sub).compile().as_text()


def phase_parity(args, mesh):
    """Step 3: every neighbour engine on a 64-instance slice. The engines
    run in threads of this process, so that one engine's compiles overlap
    the others' device time; each run is independent and deterministic."""
    from concurrent.futures import ThreadPoolExecutor

    from repro.core.aggregate import metrics_to_records

    def run(impl):
        t0 = time.perf_counter()
        cfg = sweep_config(PARITY_INSTANCES, args.seed, neighbor_impl=impl)
        runner, state, info, summary = supervised(cfg, mesh, WORKERS,
                                                  quiet=True)
        check(info["eligible_completion_rate"] == 1.0,
              f"{impl}: eligible completion "
              f"{info['eligible_completion_rate']}")
        records = metrics_to_records(
            state.metrics, state.params, scenario_ids=state.scenario_id,
            scenario_names=cfg.scenarios,
        )
        log(f"parity: {impl} done in {time.perf_counter() - t0:.1f}s")
        return runner, state, summary, records

    with ThreadPoolExecutor(len(ENGINES)) as pool:
        futures = {impl: pool.submit(run, impl) for impl in ENGINES}
        results = {impl: f.result() for impl, f in futures.items()}
    runner, state, _, _ = results["pallas"]
    check("tpu_custom_call" in pallas_chunk_text(runner, state),
          "the pallas chunk program holds no Mosaic kernel")
    log("parity: pallas chunk program holds a Mosaic kernel "
        "(tpu_custom_call)")
    _, ref_state, ref_summary, ref_records = results["reference"]
    for impl, (_, state, summary, records) in results.items():
        if not (same_json(summary, ref_summary)
                and same_json(records, ref_records)):
            raise SmokeFailure(
                f"{impl} differs from reference: "
                f"{divergence(ref_state, state)}"
            )
    log(f"parity: {', '.join(ENGINES)} give identical summaries and records")


def phase_four_chip(args, work):
    """``--four-chip``: the sharded sweep against the same sweep on one
    chip. Each chip's block holds its whole quarter of the instances
    (workers per device = instances / 4), so the block shape never changes
    and each program compiles once. Both runs model the same worker grid,
    so they draw the same fault schedule."""
    import jax
    from repro.launch.mesh import make_host_mesh

    devs = jax.devices()
    check(len(devs) >= 4, f"--four-chip needs 4 devices, found {len(devs)}")
    log_cut(FOUR_CHIP_INSTANCES)
    # ``dense``, pinned: the one-chip side pads each scenario group to all
    # 32 workers, and on ``sort`` it took 202 s for 4 of its 8 chunks on
    # a v5e. Engine parity is the default phase's check; this phase checks
    # placement and equality across devices.
    cfg = sweep_config(FOUR_CHIP_INSTANCES, args.seed, neighbor_impl="dense")
    wpd = FOUR_CHIP_INSTANCES // 4
    log(f"four-chip: {cfg.n_instances} instances x {SLOTS} slots, "
        f"{len(cfg.scenarios)} scenarios, {wpd} workers per chip, {STEPS} "
        f"steps in {CHUNK}-step chunks, fail_prob {FAIL_PROB}, "
        f"seed {cfg.seed}, {cfg.sim.neighbor_impl} engine")
    t0 = time.perf_counter()
    _, s4, i4, sum4 = supervised(cfg, make_host_mesh(4), wpd,
                                 os.path.join(work, "four"))
    t4 = time.perf_counter() - t0
    spread = len(s4.sim.pos.sharding.device_set)
    in_use = [int((d.memory_stats() or {}).get("bytes_in_use", 0))
              for d in devs[:4]]
    log(f"four-chip: {t4:.1f}s wall, {i4['chunks_run']} chunks, resting "
        f"state on {spread} devices, bytes_in_use per chip {in_use}")
    t0 = time.perf_counter()
    _, s1, i1, sum1 = supervised(cfg, make_host_mesh(1), 4 * wpd,
                                 os.path.join(work, "one"))
    log(f"one chip: {time.perf_counter() - t0:.1f}s wall, "
        f"{i1['chunks_run']} chunks")
    check(i4["eligible_completion_rate"] == 1.0 == i1["eligible_completion_rate"],
          "eligible completion below 1.0")
    if not same_json(sum4, sum1):
        raise SmokeFailure(f"four-chip summary differs from one chip: "
                           f"{divergence(s1, s4)}")
    check(same_shards(shard_contents(os.path.join(work, "four", "dataset")),
                      shard_contents(os.path.join(work, "one", "dataset"))),
          "four-chip shards differ from one chip")
    log("four-chip: summary and shards equal the one-chip run")
    check(spread == 4, f"resting state spans {spread} devices, not 4")
    check(all(b > 0 for b in in_use), f"a chip holds no bytes: {in_use}")


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        description="Run the supervised sweep on a TPU and check it.")
    ap.add_argument("--four-chip", action="store_true",
                    help="run only the sharded four-chip path and the "
                         "one-chip run it is compared with")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform "
              f"{devs[0].platform!r}); there is no CPU path",
              file=sys.stderr)
        return 2
    try:
        from repro.launch.compile_cache import enable_compile_cache
        from repro.launch.mesh import make_host_mesh
    except ImportError as e:
        print(f"chip_smoke: the repository's src/ is missing ({e})",
              file=sys.stderr)
        return 2
    log(f"device_kind {devs[0].device_kind!r}, {len(devs)} device(s)")
    log(f"compile cache: {enable_compile_cache()}")

    work = tempfile.mkdtemp(prefix=".smoke-", dir=REPO)
    t0 = time.perf_counter()
    try:
        if args.four_chip:
            phase_four_chip(args, work)
        else:
            mesh = make_host_mesh(1)
            log_cut(INSTANCES)
            summary, root = phase_sweep(args, work, mesh)
            phase_resume(args, work, mesh, summary, root)
            phase_parity(args, mesh)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    finally:
        shutil.rmtree(work, ignore_errors=True)
    log(f"all phases passed in {time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
