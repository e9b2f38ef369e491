#!/usr/bin/env python3
"""Run one cell of the benchmark once, on the chip(s) of this machine.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A cell is one entry of ``workloads`` in ``BENCHMARK.json``: a deployment
(``bench/configs/<config>.json``) under a traffic mix
(``bench/traffic/<traffic>.json``). The window drives the launcher's own
path, ``repro.launch.sweep.build_run`` -> ``repro.core.fleet.run_supervised``
(pipelined, with checkpoint manager, journal, seeded fault model and
dataset writer), on the program's default neighbour engine. The traffic
file's ``style`` picks the window:

- ``slice``: one long sweep; set-up runs the chunks that fill the road,
  the window counts the whole chunks that end within ``--seconds``;
- ``sweeps``: whole supervised sweeps back to back, each from ``init`` to
  eligible completion with its shards closed, in fresh directories;
  set-up runs the sweep once (so every program shape the window uses is
  built before it), and the window repeats it until ``--seconds`` have
  passed. A sweep with another seed meets program shapes of its own
  (group sizes, gather/scatter sizes), and would compile inside the window.

The sweep seed is the traffic file's ``sweep_seed``, the same in every
run: in this program the sweep seed draws each instance's demand, drivers,
horizon and faults together, so a seed of its own per run would change how
much work a run does. ``--seed`` draws which instances the check compares.

After the window the peak device memory is read, the program's state is
dropped, and a sample of the instances it computed is compared with the
plain reference (``check.py``). The last line of stdout is one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's end-to-end
metrics, or with ``--trace 1`` its per-layer ones), ``device``, with
``--trace 1`` a ``breakdown``, and last ``checks``: each compared number
beside its limit (also the last lines of stderr). Without a TPU, or with
fewer chips than the cell asks for, it exits 2 and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import glob  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

import numpy as np  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

import check  # noqa: E402
import reference  # noqa: E402
import spans  # noqa: E402
import tracefile  # noqa: E402


class Refused(Exception):
    """The run cannot report: no chip, too few chips, no program."""


class WindowClosed(Exception):
    """Raised from the chunk wrapper when a slice window has ended."""


def load_cell(name: str, overrides: dict | None = None):
    """(workload entry, per-layer metric entries, config, traffic)."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    with open(os.path.join(ROOT, conf["file"])) as f:
        cfg = json.load(f)
    with open(os.path.join(BENCH, "traffic", cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    for key, part in (overrides or {}).items():
        if not isinstance(part, dict):
            cfg[key] = part
            continue
        target = traffic if key == "traffic" else cfg.setdefault(key, {})
        target.update(part)
    metrics = [m for m in bench["per_layer"]
               if name in m.get("workloads", [name])]
    e2e = [m for m in bench["end_to_end"]
           if name in m.get("workloads", [name])]
    return cell, e2e, metrics, cfg, traffic


def sweep_config(cfg: dict, seed: int):
    from repro.core.record import RecordConfig
    from repro.core.scenario import SimConfig
    from repro.core.sweep import SweepConfig

    sw, roster = cfg["sweep"], tuple(cfg["roster"])
    return SweepConfig(
        n_instances=sw["n_instances"],
        steps_per_instance=sw["steps_per_instance"],
        chunk_steps=sw["chunk_steps"],
        sim=SimConfig(scenario=roster[0], **cfg["sim"]),
        seed=seed,
        vary_horizon=sw["vary_horizon"],
        min_horizon_frac=sw["min_horizon_frac"],
        scenario_mix=roster if len(roster) > 1 else (),
        dispatch=sw["dispatch"],
        record=RecordConfig(record_every=cfg["record"]["record_every"],
                            k_slots=cfg["record"]["k_slots"]),
    )


def build(cfg: dict, scfg, mesh, root: str):
    from repro.launch.sweep import build_run

    return build_run(
        scfg, mesh=mesh, workers=cfg["devices"]["workers_per_chip"],
        fail_prob=cfg["faults"]["fail_prob"],
        max_retries=cfg["faults"]["max_retries"],
        ckpt_dir=os.path.join(root, "ckpt"),
        dataset_dir=os.path.join(root, "dataset"),
        shard_size=cfg["record"]["shard_size"],
    )


class Tracer:
    """The profiler session of a traced run: started at the window's
    start, stopped at the first chunk boundary ``seconds`` later."""

    def __init__(self, on: bool, seconds: float, workdir: str) -> None:
        self.on, self.seconds = on, seconds
        self.dir = os.path.join(workdir, "trace")
        self.t0 = self.t1 = None
        self._mark = None

    def start(self) -> None:
        if not self.on:
            return
        import jax

        jax.profiler.start_trace(self.dir)
        self._mark = jax.profiler.TraceAnnotation(tracefile.WINDOW)
        self.t0 = time.perf_counter()
        self._mark.__enter__()

    def maybe_stop(self, now: float) -> None:
        if self._mark is not None and now - self.t0 >= self.seconds:
            self.stop()

    def stop(self) -> None:
        if self._mark is None:
            return
        import jax

        self._mark.__exit__(None, None, None)
        self.t1 = time.perf_counter()
        self._mark = None
        jax.profiler.stop_trace()


def answers(state, ids, every: int, rows_from=None, fields=()) -> dict:
    """The program's answers for instances ``ids``: final vehicle state
    (with the per-vehicle ``fields`` the reference's scenario modules
    compare exactly), counters, and recorded rows (from ``rows_from``
    shards when given, else from the state's trace buffer)."""
    import jax
    import jax.numpy as jnp

    if not ids:
        return {}
    idx = jnp.asarray(ids)
    sim, met, trace = jax.device_get(jax.tree.map(
        lambda x: x[idx], (state.sim, state.metrics, state.trace)))
    out = {}
    for j, i in enumerate(ids):
        t = int(sim.t[j])
        n = t // every
        rows = {k: np.asarray(getattr(trace, k)[j][:n])
                for k in ("series", "lane", "speed", "active")}
        if rows_from is not None:
            rows = rows_from.get(i, {k: v[:0] for k, v in rows.items()})
        out[i] = {
            "t": t,
            "veh": {k: np.asarray(getattr(sim, k)[j])
                    for k in ("pos", "vel", "lane", "active", *fields)},
            "counters": {k: np.asarray(getattr(met, k)[j])
                         for k in met._fields},
            **rows,
        }
    return out


def shard_rows(dataset: str, ids) -> dict:
    """Recorded rows of ``ids`` as the dataset's shards hold them."""
    want, out = set(int(i) for i in ids), {}
    for path in sorted(glob.glob(os.path.join(dataset, "shard_*.npz"))):
        with np.load(path, allow_pickle=False) as z:
            for j, i in enumerate(z["instance"].tolist()):
                if i in want:
                    n = int(z["valid_rows"][j])
                    out[i] = {k: z[k][j][:n]
                              for k in ("series", "lane", "speed", "active")}
    return out


def reference_view(ref: dict, as_program: bool = False) -> dict:
    """The reference's answers in the comparison's layout; with
    ``as_program`` its counters carry the program's names (for the control,
    where the reference stands in the program's place)."""
    def names(c, scenario):
        return ({p: c[r] for r, p in check.counter_names(scenario).items()}
                | {"speed_sum": c["speed_sum"], "min_ttc": c["min_ttc"]}
                if as_program else c)

    return {i: {"scenario": r["scenario"], "t": r["t"], "veh": r["veh"],
                "counters": names(r["counters"], r["scenario"]),
                "series": r["series"], "lane": r["lane"], "speed": r["speed"],
                "active": r["active"]} for i, r in ref.items()}


def neighbour_build(state, cfg: dict, scfg, tr_dir: str, reps: int = 20):
    """Device seconds of one neighbour-table build over every instance of
    ``state``, by the engine the program uses, read from a trace of its
    own (a stand-in until the program names the build inside its step)."""
    import jax
    from repro.core.neighbors import build_tables
    from repro.core.scenarios import get_scenario

    lanes = max(get_scenario(s).geometry(scfg.sim).n_lanes_total
                for s in scfg.scenarios)
    impl, veh_len = scfg.sim.neighbor_impl, scfg.sim.vehicle_len
    fn = jax.jit(jax.vmap(
        lambda p, l, a: build_tables(p, l, a, veh_len, lanes, impl)))
    args = (state.sim.pos, state.sim.lane, state.sim.active)
    jax.block_until_ready(fn(*args))
    jax.profiler.start_trace(tr_dir)
    with jax.profiler.TraceAnnotation(tracefile.WINDOW):
        for _ in range(reps):
            jax.block_until_ready(fn(*args))
    jax.profiler.stop_trace()
    ex = tracefile.extract(tr_dir)
    if not ex["devices"] or ex["window"] is None:
        return None
    lo, hi = ex["window"]
    busy = tracefile.busy_ns(next(iter(ex["devices"].values())), lo, hi)
    return busy / reps / 1e9 if busy else None


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def load_reader(name: str):
    path = os.path.join(BENCH, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             allow_cpu: bool = False, overrides: dict | None = None,
             plant=None) -> dict:
    """One run of one cell; returns the result object. ``allow_cpu`` and
    ``overrides`` exist for the CPU rehearsal and the tests; ``plant(runner)``
    lets a test break the timed path underneath."""
    cell, e2e, per_layer, cfg, traffic = load_cell(workload, overrides)
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        raise Refused("the program (src/repro) is not in this checkout")
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" and not allow_cpu:
        raise Refused(f"no TPU: JAX platform is {devs[0].platform!r}")
    if len(devs) < cell["chips"]:
        raise Refused(f"the cell asks for {cell['chips']} chips, "
                      f"JAX sees {len(devs)}")
    if devs[0].platform == "tpu":
        import peaks

        peaks.of(devs[0].device_kind)       # an unknown chip is an error
        from repro.launch.compile_cache import enable_compile_cache

        enable_compile_cache()

    from repro.core.aggregate import aggregate_metrics
    from repro.core.fleet import run_supervised
    from repro.launch.mesh import make_host_mesh

    scfg = sweep_config(cfg, traffic["sweep_seed"])
    mesh = make_host_mesh(max_workers=cell["chips"])
    used = list(mesh.devices.flat)
    every = cfg["record"]["record_every"]
    fields = sorted({f for s in cfg["roster"]
                     for f in reference.scenario(s).exact})
    rec = spans.Record(traced=trace)
    rec.slot_steps_per_row = scfg.chunk_steps * scfg.sim.n_slots
    rec.listen()
    work = tempfile.mkdtemp(prefix="bench-")
    tracer = Tracer(trace, traffic["trace_seconds"], work)
    rng = np.random.default_rng(seed)
    try:
        entries: list[float] = []
        speed_at: list = []

        def chunk_index() -> int:
            return len(entries) - 1

        runner, kw = build(cfg, scfg, mesh, os.path.join(work, "run0"))
        if plant is not None:
            plant(runner)
        rec.wrap_runner(runner, chunk_index)
        rec.wrap_durable(kw)
        inner = runner.run_chunk
        ctl = {"t0": None, "stop_at": None, "last": None, "fill": None}

        def run_chunk(state, hold=None):
            now = time.perf_counter()
            entries.append(now)
            speed_at.append(state.metrics.speed_count)
            if len(entries) - 1 == ctl["fill"]:         # slice window opens
                ctl["t0"] = now
                ctl["stop_at"] = now + seconds
                tracer.start()
            elif ctl["t0"] is not None:
                tracer.maybe_stop(now)
            if ctl["stop_at"] is not None and now > ctl["stop_at"]:
                ctl["last"] = state
                raise WindowClosed
            return inner(state, hold)

        runner.run_chunk = run_chunk
        rec.wrap(runner, "run_chunk")
        n = scfg.n_instances

        if traffic["style"] == "slice":
            fill = -(-traffic["fill_sim_seconds"]
                     // (scfg.chunk_steps * scfg.sim.dt))
            fill = int(fill)

            ctl["fill"] = fill
            try:
                run_supervised(runner, **kw, pipeline=True)
                raise RuntimeError("the slice sweep ended inside the window")
            except WindowClosed:
                pass
            finally:
                kw["ckpt"].wait()
            tracer.stop()
            closed = len(entries) - 1           # entry that closed the window
            last_end = closed - 1               # last chunk end inside it
            if last_end <= fill:
                raise RuntimeError("no whole chunk ended inside the window")
            rec.window = (entries[fill], entries[last_end])
            rec.window_chunks = (fill, last_end)
            rec.veh_steps = float(np.sum(jax.device_get(speed_at[last_end]))
                                  - np.sum(jax.device_get(speed_at[fill])))
            state = ctl["last"]
            sweeps_done = 0
            attempted, failed = n * (last_end - fill), 0
            ids = check.sample(n, traffic["sample"], rng)
            targets = {i: closed * scfg.chunk_steps for i in ids}
            got = answers(state, ids, every, fields=fields)
            incomplete = 0
        else:
            cap = traffic["max_chunks"]
            # ``init`` builds a new jitted program on every call, so it would
            # compile once a sweep inside the window: run it once, here, and
            # start every sweep from the state it made (arrays are immutable)
            start = runner.init()

            def one_sweep(k: int):
                root = os.path.join(work, f"sweep{k}")
                _, skw = build(cfg, scfg, mesh, root)
                rec.wrap_durable(skw)
                st, info = run_supervised(runner, **skw, state=start,
                                          pipeline=True, max_chunks=cap)
                skw["ckpt"].wait()
                summary = aggregate_metrics(st.metrics,
                                            scenario_ids=st.scenario_id,
                                            scenario_names=scfg.scenarios)
                skw["writer"].finalize(summary=summary, fault_info=info)
                return st, info, root

            state, info, root = one_sweep(0)            # set-up: warm shapes
            log(f"set-up sweep: {info['chunks_run']} chunks, "
                f"{len(info['failure_events'])} failure events, "
                f"{len(info['quarantined'])} quarantined")
            shutil.rmtree(root, ignore_errors=True)
            del state
            ctl["t0"] = time.perf_counter()
            first = len(entries)
            tracer.start()
            veh, sweeps_done, incomplete, failed = 0.0, 0, 0, 0
            while True:
                if sweeps_done:
                    shutil.rmtree(root, ignore_errors=True)
                state, info, root = one_sweep(sweeps_done + 1)
                sweeps_done += 1
                veh += float(np.sum(jax.device_get(state.metrics.speed_count)))
                done = np.asarray(jax.device_get(state.done))
                q = np.zeros(n, bool)
                q[info["quarantined"]] = True
                incomplete += int(np.sum(~done & ~q))
                failed += int(np.sum(~done))
                tracer.maybe_stop(time.perf_counter())
                if time.perf_counter() - ctl["t0"] >= seconds:
                    break
            tracer.stop()
            del start
            rec.window = (ctl["t0"], time.perf_counter())
            rec.window_chunks = (first, len(entries))
            rec.veh_steps = veh
            attempted = n * sweeps_done
            horizon = np.asarray(jax.device_get(state.horizon))
            ids = check.sample(n, traffic["sample"], rng,
                               eligible=done & ~q, longest=horizon)
            targets = {i: 10**9 for i in ids}     # each to its own horizon
            got = answers(state, ids, every,
                          rows_from=shard_rows(os.path.join(root, "dataset"),
                                               ids), fields=fields)

        t_window = rec.window[1] - rec.window[0]
        setup_s = rec.window[0] - T_START
        peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                   for d in used)
        gaps = np.diff(entries[rec.window_chunks[0]:rec.window_chunks[1] + 1])
        log(f"chunk periods in the window (s): {np.round(gaps, 3).tolist()}")
        log(f"window: {t_window:.3f}s, {rec.chunks_in_window()} chunks, "
            f"{sweeps_done} sweeps, {rec.veh_steps:.0f} live vehicle-steps; "
            f"set-up {setup_s:.3f}s; peak_bytes_in_use {peak}")
        if trace:
            rec.neighbor_build_s = neighbour_build(
                state, cfg, scfg, os.path.join(work, "nbtrace"))
            ex = tracefile.extract(tracer.dir)
            host = list(rec.spans) + [("compile", t - s, t)
                                      for t, s in rec.compiles]
            rec.device = tracefile.reduce(ex, host, tracer.t0) or {}
        del state, kw, runner
        gc.collect()

        # ---- correctness: the sample against the plain reference ----------
        t_ref = time.perf_counter()
        expected = reference.rollout(cfg, scfg.seed, ids,
                                     [targets[i] for i in ids])
        numbers = check.compare(got, reference_view(expected))
        if traffic["style"] == "sweeps":
            numbers["incomplete"] = incomplete
        ok, checks = check.judge(numbers, cfg["limits"])
        log(f"reference over {len(ids)} instances in "
            f"{time.perf_counter() - t_ref:.1f}s")

        if trace:
            values = {}
            for m in per_layer:
                v = load_reader(m["name"])(rec)
                if v is not None:
                    values[m["name"]] = {"value": float(v), "unit": m["unit"]}
        else:
            values = {
                "veh_steps_per_s": {"value": rec.veh_steps / t_window,
                                    "unit": "veh-steps/s"},
                "setup_s": {"value": setup_s, "unit": "s"},
            }
            values = {k: v for k, v in values.items()
                      if k in {m["name"] for m in e2e}}
        device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
                  "count": len(devs), "memory_peak_bytes": peak}
        result = {"correct": bool(ok), "attempted": int(attempted),
                  "failed": int(failed), "metrics": values, "device": device}
        if trace and rec.device:
            device["busy_s"] = rec.device["busy_s"]
            device["window_s"] = rec.device["window_s"]
            result["breakdown"] = {"device_ops": rec.device["device_ops"],
                                   "idle_gaps": rec.device["idle_gaps"]}
        result["checks"] = checks
        return result
    finally:
        rec.unlisten()
        shutil.rmtree(work, ignore_errors=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace))
    except Refused as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    for name, c in result["checks"].items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
