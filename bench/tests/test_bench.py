"""Checks of the benchmark's own yardstick, on the CPU.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests

- the trace reduction on small synthetic traces (busy union, idle gaps
  by innermost host span, nothing to read without device operations);
- the control: the plain reference in bfloat16, put in the program's
  place, fails the comparison of every cell (at a size a test holds);
- a sound run of every cell is correct, and each fault the cell can have,
  planted under the timed path, makes the run not correct (subprocesses on
  four virtual CPU devices, through ``rehearse.py``).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import tracefile  # noqa: E402

def test_reduction_matches_a_brute_force_count():
    import numpy as np

    rng = np.random.default_rng(7)
    ex = {"window": [1000.0, 9000.0], "devices": {}}
    for d in range(2):
        ex["devices"][f"/device:TPU:{d}"] = [
            [f"op{int(k)}", float(s), float(w)]
            for k, s, w in zip(rng.integers(0, 5, 300),
                               rng.integers(0, 10000, 300),
                               rng.integers(1, 80, 300))]
    got = tracefile.reduce(ex, top=1000)
    grid = np.arange(1000.0, 9000.0) + 0.5     # whole-ns cells, exact here
    busy = []
    for ops in ex["devices"].values():
        on = np.zeros(grid.size, bool)
        for _, s, w in ops:
            on |= (grid >= s) & (grid < s + w)
        busy.append(float(on.sum()))
    assert got["busy_s"] * 1e9 == pytest.approx(np.mean(busy), abs=1e-6)
    idle = 8000.0 - busy[0]
    assert sum(v for _, v in got["idle_gaps"]) * 1e9 == pytest.approx(
        idle, abs=1e-6)
    assert got["window_s"] == pytest.approx(8000e-9)


def test_reduction_attributes_gaps_to_innermost_span():
    ex = {"window": [0.0, 100.0],
          "devices": {"/device:TPU:0": [["op", 10.0, 20.0],
                                        ["op", 60.0, 20.0]]}}
    spans = [("chunk", 25e-9, 70e-9), ("plan", 40e-9, 50e-9)]
    got = tracefile.reduce(ex, spans, perf_at_window=0.0)
    gaps = dict((k, v * 1e9) for k, v in got["idle_gaps"])
    assert gaps == pytest.approx({"other": 30.0, "chunk": 20.0, "plan": 10.0})
    assert got["busy_s"] == pytest.approx(40e-9)


def test_reduction_finds_nothing_without_device_ops():
    assert tracefile.reduce({"window": [0.0, 1.0], "devices": {}}) is None


TINY = {"sweep": {"n_instances": 8, "steps_per_instance": 300},
        "sim": {"n_slots": 24}, "traffic": {"fill_sim_seconds": 20,
                                            "sample": 4}}


@pytest.mark.parametrize("cell", ["merge256.slice", "mix64.sweeps"])
def test_control_fails(cell):
    import control

    over = json.loads(json.dumps(TINY))
    if cell.endswith("slice"):
        over["sweep"]["steps_per_instance"] = 9000
    r = control.control(cell, 2**31 + 5, chunks=1, overrides=over)
    assert r["correct"] is False
    assert r["checks"]["pos_gap_m"]["value"] > r["checks"]["pos_gap_m"]["limit"]


CASES = [("merge256.slice", None), ("merge256.slice", "unchanged"),
         ("merge256.slice", "half"), ("merge256.slice", "altered"),
         ("mix64.sweeps", None), ("mix64.sweeps", "unchanged"),
         ("mix64.sweeps", "half"), ("mix64.sweeps", "altered")]


@pytest.mark.parametrize("cell,fault", CASES)
def test_run_is_correct_unless_a_fault_is_planted(cell, fault):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    cmd = [sys.executable, os.path.join(BENCH, "rehearse.py"),
           "--cells", cell, "--seconds", "1"]
    if fault:
        cmd += ["--fault", fault]
    p = subprocess.run(cmd, env=env, capture_output=True, text=True,
                       timeout=900)
    lines = [json.loads(x) for x in p.stdout.splitlines()
             if x.startswith("{")]
    result = [x for x in lines if x.get("cell") == cell][-1]
    assert result["correct"] is (fault is None), result["checks"]
    assert p.returncode == 0, p.stderr[-2000:]
