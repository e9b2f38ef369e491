"""Checks of the readings of the program's own spans and device scopes
(``phases.py``), on small synthetic traces, on the CPU.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests/test_phases.py

- self time by scope against a brute-force count on a nested trace: the
  phases plus ``unscoped`` add up to the busy union;
- the innermost phase scope of an ``op_name`` path;
- idle gaps under program spans handed over on the trace's clock: finer
  labels, the same total;
- host readings count only chunks inside the given part, executor self
  time without the spans nested in it;
- the probe itself (``--tiny``) on one cell at the CPU rehearsal's size.
"""

from __future__ import annotations

import os
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import phases  # noqa: E402

SCOPES = phases.PHASES + (phases.UNSCOPED,)


def nested_ops(rng, lo, hi, depth, out):
    """Ops that nest like a device line: siblings in sequence, children
    inside their parent, on whole nanoseconds."""
    t = lo
    while t < hi - 2:
        a = int(rng.integers(t, min(t + 40, hi - 1)))
        b = int(rng.integers(a + 1, min(a + 200, hi) + 1))
        scope = SCOPES[int(rng.integers(len(SCOPES)))]
        out.append([scope, float(a), float(b - a), depth])
        if depth < 3 and b - a > 4:
            nested_ops(rng, a, b, depth + 1, out)
        t = b
    return out


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_self_time_by_scope_matches_a_brute_force_count(seed):
    rng = np.random.default_rng(seed)
    ops = nested_ops(rng, 0, 5000, 0, [])
    lo, hi = 300.0, 4700.0                      # the window cuts ops
    got = phases.self_time_by_scope([o[:3] for o in ops], lo, hi)
    cells = np.arange(lo, hi) + 0.5
    owner = np.full(cells.size, -1)
    depth = np.full(cells.size, -1)
    for k, (_, s, d, dep) in enumerate(ops):
        on = (cells >= s) & (cells < s + d) & (dep > depth)
        owner[on], depth[on] = k, dep
    want = {}
    for k in owner[owner >= 0]:
        want[ops[k][0]] = want.get(ops[k][0], 0.0) + 1.0
    assert got == pytest.approx(want, abs=1e-6)
    busy = phases.tracefile.busy_ns([o[:3] for o in ops], lo, hi)
    assert sum(got.values()) == pytest.approx(busy, abs=1e-6)


@pytest.mark.parametrize("op_name,scope", [
    ("jit(jit_block_uniform)/jit(main)/while/body/neighbors/sort",
     "neighbors"),
    ("jit(f)/while/body/lane_change/neighbors/gather", "neighbors"),
    ("jit(f)/while/body/lane_change/mul", "lane_change"),
    ("jit(f)/vmap(while)/body/jvp(spawn)/scatter", "spawn"),
    ("jit(f)/while/body/record/dynamic_update_slice", "record"),
    ("jit(f)/while/body/select_n", "unscoped"),
    ("jit(neighbors)", "unscoped"),
])
def test_scope_of_finds_the_innermost_phase(op_name, scope):
    assert phases.scope_of(op_name) == scope


def test_op_scope_from_the_program_hlo():
    hlo = {7: ("jit_block", {"fusion.6": "jit(block)/while/body/spawn/add",
                             "while.2": "jit(block)/while"}),
           9: ("jit_gather", {"fusion.6": "jit(gather)/gather"})}
    ops = phases.OpScopes(hlo)
    text = "%fusion.6 = f32[8]{0:T(1024)} fusion(f32[8]{0} %p), kind=kLoop"
    assert phases.instruction(text) == "fusion.6"
    assert ops.scope("jit_block(7)", text) == "spawn"
    assert ops.scope("jit_gather(9)", text) == "unscoped"
    assert ops.scope("jit_block", "while.2") == "unscoped"
    assert ops.scope(None, 'x = f32[] add(), metadata={op_name="a/record/b"}'
                     ) == "record"
    assert ops.scope("jit_other(3)", "copy.1") is None


def test_hlo_op_names_from_a_recorded_cpu_trace(tmp_path):
    import glob

    import jax
    import jax.numpy as jnp

    @jax.jit
    def f(x):
        with jax.named_scope("neighbors"):
            y = jnp.sort(x)
        with jax.named_scope("spawn"):
            return y * 2 + 1

    x = jnp.arange(64.0)
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    f(x).block_until_ready()
    jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)[0]
    with open(path, "rb") as fh:
        hlo = phases.hlo_op_names(fh.read())
    scopes = {phases.scope_of(op) for _, names in hlo.values()
              for op in names.values()}
    assert {"neighbors", "spawn"} <= scopes
    assert any(module == "jit_f" for module, _ in hlo.values())


T0 = 5e9                                      # a trace clock's origin


def synthetic():
    ops = [["x", T0 + 10, 20.0], ["y", T0 + 60, 20.0]]
    return {
        "window": [T0, T0 + 100],
        "devices": {"/device:TPU:0": ops},
        "ops": {"/device:TPU:0": [["spawn", T0 + 10, 20.0],
                                  ["unscoped", T0 + 60, 20.0]]},
        "modules": {"/device:TPU:0": [T0 + 10, T0 + 60, T0 + 95]},
        "spans": [["chunk", T0 + 25, T0 + 70],
                  ["sweep.chunk", T0 + 26, T0 + 70],
                  ["sweep.gather", T0 + 30, T0 + 45],
                  ["sweep.chunk", T0 + 90, T0 + 99],
                  ["plan", T0 + 40, T0 + 50]],
    }


def test_idle_gaps_under_program_spans_on_the_trace_clock():
    both, alone = phases.idle_gaps(synthetic())
    both = {k: v * 1e9 for k, v in both}
    alone = {k: v * 1e9 for k, v in alone}
    assert alone == pytest.approx({"other": 30.0, "chunk": 20.0,
                                   "plan": 10.0}, abs=1e-3)
    assert both == pytest.approx({"other": 21.0, "sweep.gather": 10.0,
                                  "plan": 10.0, "sweep.chunk": 19.0},
                                 abs=1e-3)
    assert sum(both.values()) == pytest.approx(sum(alone.values()), abs=1e-3)


def test_device_readings_per_chunk_entry():
    got = phases.device_readings(synthetic())
    assert got["chunks"] == 2
    assert got["spawn_ms_per_chunk"] == pytest.approx(10e-6)
    assert got["unscoped_ms_per_chunk"] == pytest.approx(10e-6)
    assert got["neighbors_ms_per_chunk"] == 0.0
    assert got["programs_per_chunk"] == 1.5
    assert got["busy_ms_per_chunk"] == pytest.approx(
        got["chip0_scopes_ms_per_chunk"])


def test_host_readings_count_the_chunks_of_their_part():
    spans = [("sweep.chunk", 0.0, 1.0), ("sweep.sync", 0.1, 0.2),
             ("sweep.plan", 0.2, 0.3), ("sweep.step", 0.3, 0.5),
             ("fleet.sync", 1.0, 1.5),
             ("sweep.chunk", 2.0, 3.0), ("sweep.gather", 2.0, 2.4),
             ("sweep.sync", 2.1, 2.2), ("fleet.sync", 3.0, 3.25)]
    after = phases.host_readings(spans, 1.5, 9.0)
    assert after["chunks"] == 1
    assert after["exec_host_ms_per_chunk"] == pytest.approx(300.0)
    assert after["sync_wait_ms_per_chunk"] == pytest.approx(350.0)
    both = phases.host_readings(spans, 0.0, 9.0)
    assert both["exec_host_ms_per_chunk"] == pytest.approx(300.0)
    assert phases.host_readings(spans, 3.5, 9.0)[
        "exec_host_ms_per_chunk"] is None


def test_probe_rehearses_a_cell_on_the_cpu(tmp_path):
    seed = 2**31 + 5
    out = phases.probe("mix64.sweeps", seed, 1.0, tiny=True,
                       out_dir=str(tmp_path))
    assert out["correct"] and out["device"]["platform"] == "cpu"
    assert set(out["phases"]) == (
        {f"{p}_ms_per_chunk" for p in SCOPES}
        | {"programs_per_chunk", "exec_host_ms_per_chunk",
           "sync_wait_ms_per_chunk"})
    assert out["resolved"] is None        # a CPU trace has no TPU plane
    assert out["hlo_programs"] > 0
    assert out["profiled"]["chunks"] > 0
    assert out["profiled"]["exec_host_ms_per_chunk"] > 0
    assert out["compiles"] == []          # set-up built every program
    assert (tmp_path / f"phases_mix64.sweeps_{seed}.json").exists()
