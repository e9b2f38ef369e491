"""The sweep's spans, counters and device scopes (``repro.core.trace``).

A tiny grouped sweep with faults, recording, checkpoints and a journal is
run once with ``jax.monitoring`` listeners attached; the tests read what
the listeners saw:

- each ``run_chunk`` gives one ``sweep.chunk`` span and its executor
  spans nest inside it;
- every ``device_get`` of the run loop sits inside a ``*.sync`` span (or
  inside the durable write that copies state out);
- the ``sweep.slot_steps`` counter equals what the plans' ``take`` sizes
  give;
- it names the neighbour engine the slot-steps ran on;
- listening changes no bit of the sweep;
- the lowered chunk program names the five device phases.
"""

import dataclasses
import json
import os
import re
import subprocess
import sys
import textwrap
import time

import jax
import pytest
from conftest import assert_states_equal

from repro.ckpt import CheckpointManager
from repro.core import SimConfig
from repro.core.fault import FaultModel
from repro.core.fleet import RunJournal, run_supervised
from repro.core.neighbors import resolve_impl
from repro.core.record import RecordConfig, batch_zeros
from repro.core.simulator import rollout_chunk_rec
from repro.core.sweep import SweepConfig, SweepRunner
from repro.core.trace import PHASES, count, span
from repro.data.shards import DatasetWriter

CFG = SweepConfig(
    n_instances=8, steps_per_instance=120, chunk_steps=40,
    sim=SimConfig(n_slots=16), seed=5, vary_horizon=True,
    scenario_mix=("highway_merge", "lane_drop"), dispatch="grouped",
    record=RecordConfig(record_every=10, k_slots=4),
)
FAULTS = {0: [1], 1: [0, 3]}
EXECUTOR = ("sweep.sync", "sweep.plan", "sweep.gather", "sweep.step",
            "sweep.scatter")
DURABLE = ("fleet.ckpt", "fleet.drain", "fleet.audit")


class Listener:
    """Spans and counters as ``jax.monitoring`` delivers them."""

    def __init__(self):
        self.spans, self.scalars, self.scalar_meta = [], [], []

    def _span(self, name, t0, t1, **meta):
        self.spans.append((name, t0, t1, meta))

    def _scalar(self, name, value, **meta):
        self.scalars.append((name, value))
        self.scalar_meta.append((name, meta))

    def __enter__(self):
        jax.monitoring.register_event_time_span_listener(self._span)
        jax.monitoring.register_scalar_listener(self._scalar)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_time_span_listener(self._span)
        jax.monitoring.unregister_scalar_listener(self._scalar)

    def named(self, *names):
        return [s for s in self.spans if s[0] in names]


def _supervised(root, runner=None):
    runner = runner or SweepRunner(CFG, workers_per_device=2)
    return run_supervised(
        runner, FaultModel(4, {k: list(v) for k, v in FAULTS.items()}),
        ckpt=CheckpointManager(os.path.join(root, "ck"), async_write=False),
        writer=DatasetWriter(os.path.join(root, "ds"), CFG, shard_size=2),
        journal=RunJournal(os.path.join(root, "j.jsonl")),
        pipeline=True,
    )


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """One listened run: (listener, device_get intervals, plans, state,
    info)."""
    root = str(tmp_path_factory.mktemp("traced"))
    runner = SweepRunner(CFG, workers_per_device=2)
    plans = []
    inner_plan = runner.plan_chunk

    def plan_chunk(state, hold=None):
        out = inner_plan(state, hold)
        plans.append(out)
        return out

    runner.plan_chunk = plan_chunk
    gets = []
    real_get = jax.device_get

    def device_get(x):
        t0 = time.perf_counter()
        out = real_get(x)
        gets.append((t0, time.perf_counter()))
        return out

    mp = pytest.MonkeyPatch()
    mp.setattr(jax, "device_get", device_get)
    try:
        with Listener() as lis:
            state, info = _supervised(root, runner)
    finally:
        mp.undo()
    return lis, gets, plans, state, info


def _inside(t0, t1, spans):
    return [s for s in spans if s[1] <= t0 and t1 <= s[2]]


def test_span_records_name_times_and_meta():
    with Listener() as lis:
        with span("fleet.ckpt", chunk=3):
            with span("sweep.sync"):
                pass
        count("sweep.slot_steps", 640)
    (inner, *_), (outer, *_) = lis.spans[0], lis.spans[1]
    assert (inner, outer) == ("sweep.sync", "fleet.ckpt")
    assert lis.spans[1][3] == {"chunk": 3}
    assert lis.spans[1][1] <= lis.spans[0][1] <= lis.spans[0][2] \
        <= lis.spans[1][2]
    assert lis.scalars == [("sweep.slot_steps", 640)]


def test_one_chunk_span_per_run_chunk_with_executor_spans_inside(traced):
    lis, _, _, _, info = traced
    chunks = lis.named("sweep.chunk")
    assert len(chunks) == info["chunks_run"] > 2
    executor = lis.named(*EXECUTOR)
    assert {s[0] for s in executor} == set(EXECUTOR)
    for name, t0, t1, _ in executor:
        assert len(_inside(t0, t1, chunks)) == 1, name
    for _, t0, t1, _ in chunks:
        inside = [s[0] for s in executor if t0 <= s[1] and s[2] <= t1]
        assert "sweep.sync" in inside and "sweep.plan" in inside


def test_fleet_spans_carry_the_chunk_they_commit(traced):
    lis, _, _, _, info = traced
    for name in ("fleet.revert", "fleet.ckpt", "fleet.drain", "fleet.audit",
                 "fleet.journal"):
        got = [s[3].get("chunk") for s in lis.named(name)]
        assert got, name
        assert all(c is None or 0 <= c < info["chunks_run"] for c in got)
    assert sorted(s[3]["chunk"] for s in lis.named("fleet.ckpt")) == list(
        range(info["chunks_run"]))


def test_every_device_get_of_the_loop_sits_in_a_sync_span(traced):
    lis, gets, _, _, _ = traced
    loop = lis.named("fleet.sync")
    lo, hi = loop[0][1], loop[-1][2]
    syncs = lis.named("sweep.sync", "fleet.sync")
    durable = lis.named(*DURABLE)
    in_loop = [g for g in gets if lo <= g[0] and g[1] <= hi]
    assert len(in_loop) > len(lis.named("sweep.chunk"))
    for t0, t1 in in_loop:
        assert _inside(t0, t1, syncs) or _inside(t0, t1, durable)
    for _, t0, t1, _ in lis.named("sweep.chunk"):
        for g in gets:
            if t0 <= g[0] and g[1] <= t1:
                assert _inside(*g, lis.named("sweep.sync"))


def test_slot_steps_counter_matches_the_plans(traced):
    lis, _, plans, _, _ = traced
    per_row = CFG.chunk_steps * CFG.sim.n_slots
    want = sum(p.take.size for ps in plans for p in ps) * per_row
    got = [v for name, v in lis.scalars if name == "sweep.slot_steps"]
    assert len(got) == sum(len(ps) for ps in plans)
    assert sum(got) == want > 0


@pytest.mark.parametrize("impl", ["auto", "dense"])
def test_slot_steps_counter_names_the_resolved_engine(traced, impl):
    """``sweep.slot_steps`` carries ``impl=``, the engine the slot-steps
    ran on: ``auto`` resolved from the slot count and the backend, a
    pinned engine as it is named."""
    if impl == "auto":
        lis = traced[0]
        sim = CFG.sim
    else:
        sim = dataclasses.replace(CFG.sim, neighbor_impl=impl)
        runner = SweepRunner(dataclasses.replace(CFG, sim=sim),
                             workers_per_device=2)
        with Listener() as lis:
            runner.run_chunk(runner.init())
    want = resolve_impl(sim.neighbor_impl, sim.n_slots, jax.default_backend())
    metas = [m for name, m in lis.scalar_meta if name == "sweep.slot_steps"]
    assert metas and all(m == {"impl": want} for m in metas), metas


def test_listening_changes_no_bit_of_the_sweep(traced, tmp_path):
    _, _, _, state, info = traced
    quiet, quiet_info = _supervised(str(tmp_path))
    assert_states_equal(state, quiet)
    assert quiet_info["failure_events"] == info["failure_events"]


def test_lowered_chunk_names_the_device_phases():
    st = SweepRunner(CFG).init()
    one = jax.tree.map(lambda x: x[0], (st.sim, st.metrics, st.params,
                                        st.horizon))
    tr = jax.tree.map(lambda x: x[0],
                      batch_zeros(CFG.record, CFG.steps_per_instance, 1))
    text = rollout_chunk_rec.lower(
        *one, tr, cfg=CFG.sim, n_steps=CFG.chunk_steps, rec=CFG.record,
    ).as_text(debug_info=True)
    scopes = {part for loc in re.findall(r'loc\("([^"]*)"', text)
              for part in loc.split("/")[:-1]}
    assert set(PHASES) <= scopes


def test_block_executor_spans_on_four_devices():
    """The sharded executor (one ``shard_map`` call a chunk) emits the same
    spans; four virtual CPU devices in a child process."""
    code = textwrap.dedent("""
        import json, jax, numpy as np
        from jax.sharding import Mesh
        from repro.core import SimConfig
        from repro.core.sweep import SweepConfig, SweepRunner
        seen, counts = [], []
        jax.monitoring.register_event_time_span_listener(
            lambda n, a, b, **m: n.startswith("sweep.") and seen.append(n))
        jax.monitoring.register_scalar_listener(
            lambda n, v, **m: n == "sweep.slot_steps" and counts.append(v))
        cfg = SweepConfig(n_instances=8, steps_per_instance=40,
                          chunk_steps=20, sim=SimConfig(n_slots=8), seed=2,
                          scenario_mix=("highway_merge", "lane_drop"))
        mesh = Mesh(np.asarray(jax.devices()[:4]), ("workers",))
        runner = SweepRunner(cfg, mesh=mesh)
        st = runner.run_chunk(runner.init())
        print(json.dumps({"spans": seen, "slot_steps": counts}))
    """)
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join(
                   [src, os.environ.get("PYTHONPATH", "")]))
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    out = json.loads(p.stdout.splitlines()[-1])
    assert out["spans"] == ["sweep.sync", "sweep.plan", "sweep.gather",
                            "sweep.step", "sweep.scatter", "sweep.chunk"]
    assert out["slot_steps"] == [8 * 20 * 8]
