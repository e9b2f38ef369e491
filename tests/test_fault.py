"""Fault tolerance: injected node failures, checkpoint/restart, elastic remesh.

Reproduces the paper's §5.2 claim — 100 % completion — under conditions the
paper never tested: nodes dying mid-slice and restarts from disk.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.ckpt import CheckpointManager
from repro.core import SimConfig
from repro.core.fault import FailureInjector, run_with_failures, revert_instances
from repro.core.sweep import SweepConfig, SweepRunner, completion_rate

SIM = SimConfig(n_slots=16)


def _cfg(**kw):
    base = dict(
        n_instances=8,
        steps_per_instance=120,
        chunk_steps=40,
        sim=SIM,
        seed=11,
    )
    base.update(kw)
    return SweepConfig(**base)


def test_failures_still_reach_full_completion():
    runner = SweepRunner(_cfg())
    injector = FailureInjector(n_workers=4, plan={0: [1], 1: [0, 3], 3: [2]})
    state, info = run_with_failures(runner, injector)
    assert info["completion_rate"] == 1.0
    assert len(info["failure_events"]) == 3
    # failures force extra chunks beyond the failure-free 3
    assert info["chunks_run"] > 3


def test_failed_run_metrics_match_clean_run():
    """Re-executed instances produce byte-identical results (determinism)."""
    clean = SweepRunner(_cfg()).run()
    runner = SweepRunner(_cfg())
    injector = FailureInjector(n_workers=4, plan={0: [0], 2: [1, 2]})
    state, info = run_with_failures(runner, injector)
    assert info["completion_rate"] == 1.0
    for a, b in zip(jax.tree.leaves(clean.metrics),
                    jax.tree.leaves(state.metrics)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_random_failure_storm_completes():
    runner = SweepRunner(_cfg(n_instances=6))
    injector = FailureInjector.random(
        n_workers=3, n_chunks=4, fail_prob=0.4, seed=5
    )
    state, info = run_with_failures(runner, injector, max_chunks=60)
    assert info["completion_rate"] == 1.0


def test_checkpoint_restart_resumes(tmp_path):
    cfg = _cfg()
    ckpt = CheckpointManager(str(tmp_path / "sweep"), async_write=False)
    runner = SweepRunner(cfg)

    # run only the first chunk, checkpointing
    state = runner.init()
    state = runner.run_chunk(state)
    ckpt.save(int(jax.device_get(state.chunk)), state)

    # "job killed" — fresh runner restores from disk and finishes
    runner2 = SweepRunner(cfg)
    injector = FailureInjector(n_workers=4, plan={})
    state2, info = run_with_failures(runner2, injector, ckpt=ckpt)
    assert info["completion_rate"] == 1.0

    # equal to a never-interrupted run
    clean = SweepRunner(cfg).run()
    for a, b in zip(jax.tree.leaves(clean.metrics),
                    jax.tree.leaves(state2.metrics)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


from conftest import assert_states_equal as _assert_states_equal

MIX = ("highway_merge", "lane_drop", "stop_and_go", "speed_limit_zone")


@pytest.mark.parametrize("compaction", [True, False])
def test_failure_parity_grouped_vs_switch(compaction):
    """Failure masks address LOGICAL instance ids, so the same injection
    plan kills the same instances under either dispatch mode and the full
    final states are bit-for-bit equal — the planner's physical repacking
    never leaks into fault semantics."""
    plan = {0: [0], 1: [2, 3], 3: [1]}
    finals = {}
    for dispatch in ("switch", "grouped"):
        runner = SweepRunner(_cfg(scenario_mix=MIX, compaction=compaction,
                                  dispatch=dispatch))
        injector = FailureInjector(n_workers=4, plan=dict(plan))
        finals[dispatch], info = run_with_failures(runner, injector)
        assert info["completion_rate"] == 1.0
        assert len(info["failure_events"]) == 3
    _assert_states_equal(finals["switch"], finals["grouped"])


@pytest.mark.parametrize("dispatch", ["switch", "grouped"])
def test_checkpoint_roundtrip_resume_parity(dispatch, tmp_path):
    """A mid-sweep SweepState survives a CheckpointManager round trip and
    the resumed run finishes bit-identical to a never-interrupted run, under
    both dispatch modes."""
    cfg = _cfg(scenario_mix=MIX, vary_horizon=True, min_horizon_frac=0.3,
               dispatch=dispatch)
    ckpt = CheckpointManager(str(tmp_path / "sw"), async_write=False)

    runner = SweepRunner(cfg)
    state = runner.init()
    state = runner.run_chunk(state)
    ckpt.save(int(jax.device_get(state.chunk)), state)

    # the restored tree is bit-identical to what was saved
    restored, meta = ckpt.restore(like=state)
    _assert_states_equal(state, restored)
    assert meta["step"] == 1

    # "job killed" — a fresh runner resumes from disk and finishes
    runner2 = SweepRunner(cfg)
    final, info = run_with_failures(
        runner2, FailureInjector(n_workers=4, plan={}), ckpt=ckpt
    )
    assert info["completion_rate"] == 1.0
    clean = SweepRunner(cfg).run()
    _assert_states_equal(clean, final)


def test_revert_instances_partial():
    runner = SweepRunner(_cfg())
    s0 = runner.init()
    s1 = runner.run_chunk(s0)
    mask = np.zeros(8, bool)
    mask[:4] = True
    reverted = revert_instances(s1, s0, mask)
    t = np.asarray(jax.device_get(reverted.sim.t))
    assert (t[:4] == 0).all()          # reverted to snapshot
    assert (t[4:] == 40).all()         # kept chunk progress


def test_elastic_remesh_noop_on_single_device():
    """Remesh keeps logical state intact (single-device degenerate case)."""
    def to_np(x):
        if jax.dtypes.issubdtype(x.dtype, jax.dtypes.prng_key):
            x = jax.random.key_data(x)
        return np.asarray(jax.device_get(x))

    runner = SweepRunner(_cfg())
    state = runner.init()
    state = runner.run_chunk(state)
    before = jax.tree.map(to_np, state)
    mesh = jax.make_mesh((1,), ("workers",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    state2 = runner.remesh(state, mesh)
    after = jax.tree.map(to_np, state2)
    for a, b in zip(jax.tree.leaves(before), jax.tree.leaves(after)):
        np.testing.assert_array_equal(a, b)
    # and the sweep still completes on the new mesh
    final = runner.run(state2)
    assert completion_rate(final) == 1.0


# --------------------------------------------------------------------------
# trajectory recording under faults: the dispatch-agnostic, resume-exact
# dataset channel (repro.core.record)
# --------------------------------------------------------------------------

from repro.core.record import RecordConfig

REC = RecordConfig(record_every=10, k_slots=4)
MIX2 = ("highway_merge", "lane_drop")
_REC_KW = dict(n_instances=8, steps_per_instance=80, chunk_steps=40,
               sim=SIM, seed=11, scenario_mix=MIX2, record=REC)


@pytest.mark.parametrize("dispatch", ["switch", "grouped"])
def test_recording_parity_under_injected_failures(dispatch):
    """Node failures revert instances to their chunk snapshot; the re-run
    rewrites the SAME trace rows with identical values, so the final
    recorded dataset is bit-for-bit equal to a failure-free run — under
    both dispatch modes."""
    clean = SweepRunner(SweepConfig(**_REC_KW)).run()
    runner = SweepRunner(SweepConfig(dispatch=dispatch, **_REC_KW))
    injector = FailureInjector(n_workers=4, plan={0: [0], 1: [2, 3]})
    state, info = run_with_failures(runner, injector)
    assert info["completion_rate"] == 1.0
    assert len(info["failure_events"]) == 2
    # failures force extra walltime slices; everything else — trace
    # included — must match the clean run bitwise
    _assert_states_equal(clean, state._replace(chunk=clean.chunk))


@pytest.mark.parametrize("dispatch", ["switch", "grouped"])
def test_recording_checkpoint_kill_resume_parity(dispatch, tmp_path):
    """A mid-sweep kill/resume through CheckpointManager neither drops nor
    duplicates recorded rows: the resumed run's full state — trace buffer
    included — is bit-identical to a never-interrupted run."""
    cfg = SweepConfig(dispatch=dispatch, vary_horizon=True,
                      min_horizon_frac=0.3, **_REC_KW)
    ckpt = CheckpointManager(str(tmp_path / "sw"), async_write=False)

    runner = SweepRunner(cfg)
    state = runner.init()
    state = runner.run_chunk(state)
    ckpt.save(int(jax.device_get(state.chunk)), state)

    # the restored tree (trace included) is bit-identical to what was saved
    restored, meta = ckpt.restore(like=state)
    _assert_states_equal(state, restored)

    # "job killed" — a fresh runner resumes from disk and finishes
    final, info = run_with_failures(
        SweepRunner(cfg), FailureInjector(n_workers=4, plan={}), ckpt=ckpt
    )
    assert info["completion_rate"] == 1.0
    clean = SweepRunner(cfg).run()
    _assert_states_equal(clean, final)
