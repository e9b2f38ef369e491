"""Checks of the plain reference's scenario modules, on the CPU.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests/test_reference.py

- each scenario module against the program, through ``run.run_cell``'s
  comparison path at a tiny size: every compared number 0;
- a scenario added as a new file only: a copy of ``highway_merge`` with a
  per-vehicle field, a drawn vehicle length and a counter of its own,
  found through the module directory; the extensions change no bit of
  the shared state, the field and the counter come back from ``rollout``,
  and ``check.compare`` reads them;
- a drawn vehicle length is a length the physics uses;
- an unknown scenario name fails and lists the known ones.
"""

from __future__ import annotations

import copy
import os
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import check  # noqa: E402
import reference  # noqa: E402
import run  # noqa: E402

SCENARIOS = ("highway_merge", "lane_drop", "stop_and_go", "speed_limit_zone")
TINY = {"sweep": {"n_instances": 8, "steps_per_instance": 300},
        "sim": {"n_slots": 24}, "devices": {"workers_per_chip": 2},
        "traffic": {"sample": 4}}


def test_one_module_per_scenario_of_the_program():
    sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))
    from repro.core.scenarios import list_scenarios

    assert reference.known() == sorted(list_scenarios()) == sorted(SCENARIOS)


@pytest.mark.parametrize("name", SCENARIOS)
def test_scenario_module_matches_the_program(name):
    over = copy.deepcopy(TINY)
    over["roster"] = [name]
    r = run.run_cell("mix64.sweeps", 2**31 + 23, 0.5, False, allow_cpu=True,
                     overrides=over)
    assert r["correct"] is True, r["checks"]
    assert all(c["value"] == 0 for c in r["checks"].values()), r["checks"]


TAGGED = '''

FIELDS = {"entry_lane": -1}
EXACT = ("entry_lane",)
COUNTERS = {"ramp_exits": "ramp_exits"}


def draw(key, sim, rd, p, lanes, cav):
    return {"entry_lane": lanes,
            "length": jnp.full(lanes.shape, LENGTH, jnp.float32)}


def tally(v, sim, rd, p, exited, crashed):
    return {"ramp_exits": exited & (v.extra["entry_lane"] == rd.n_lanes)}
'''


def tiny_cfg(roster):
    cfg = run.load_cell("merge256.slice")[3]
    cfg["roster"] = roster
    cfg["sim"]["n_slots"] = 24
    cfg["sweep"]["n_instances"] = 4
    return cfg


@pytest.fixture
def added(tmp_path, monkeypatch):
    """A scenario directory that holds ``highway_merge`` and two copies of
    it with the extensions: ``merge_tagged`` (its vehicles' lengths drawn
    as the configuration's) and ``merge_long`` (drawn 12 m long)."""
    with open(os.path.join(reference.SCENARIOS, "highway_merge.py")) as f:
        src = f.read()
    vl = tiny_cfg([])["sim"]["vehicle_len"]
    (tmp_path / "highway_merge.py").write_text(src)
    (tmp_path / "merge_tagged.py").write_text(
        src + TAGGED.replace("LENGTH", repr(vl)))
    (tmp_path / "merge_long.py").write_text(
        src + TAGGED.replace("LENGTH", "12.0"))
    monkeypatch.setattr(reference, "SCENARIOS", str(tmp_path))
    return tmp_path


def test_a_new_module_adds_a_field_a_length_and_a_counter(added):
    ids, targets = [0, 1, 2, 3], [900] * 4   # ramp vehicles reach the end
    cfg = tiny_cfg(["merge_tagged"])
    got = reference.rollout(cfg, 7, ids, targets)
    plain = reference.rollout(tiny_cfg(["highway_merge"]), 7, ids, targets)
    for i in ids:
        for part in ("veh", "counters"):
            for f, x in plain[i][part].items():
                assert np.array_equal(got[i][part][f], x), (i, part, f)
        for k in ("series", "lane", "speed", "active"):
            assert np.array_equal(got[i][k], plain[i][k]), (i, k)
        veh = got[i]["veh"]
        assert set(veh["entry_lane"][veh["active"]]) <= {0, 1, 2, 3}
    ramp_exits = sum(int(got[i]["counters"]["ramp_exits"]) for i in ids)
    assert 0 < ramp_exits <= sum(int(got[i]["counters"]["throughput"])
                                 for i in ids)

    expected = run.reference_view(got)
    answers = run.reference_view(got, as_program=True)
    assert check.counter_names("merge_tagged")["ramp_exits"] == "ramp_exits"
    assert check.compare(answers, expected)["counter_gap"] == 0
    assert check.compare(answers, expected)["slot_mismatch"] == 0
    answers[ids[0]]["counters"]["ramp_exits"] = (
        answers[ids[0]]["counters"]["ramp_exits"] + 1)
    assert check.compare(answers, expected)["counter_gap"] == 1
    veh = answers[ids[1]]["veh"]
    answers[ids[1]]["veh"] = dict(veh, entry_lane=np.where(
        veh["active"], veh["entry_lane"] + 1, veh["entry_lane"]))
    assert check.compare(answers, expected)["slot_mismatch"] == int(
        np.sum(veh["active"]))


def test_a_drawn_length_is_the_length_the_physics_uses(added):
    ids, targets = [0, 1], [300] * 2
    long = reference.rollout(tiny_cfg(["merge_long"]), 7, ids, targets)
    plain = reference.rollout(tiny_cfg(["highway_merge"]), 7, ids, targets)
    for i in ids:
        veh = long[i]["veh"]
        assert np.all(veh["length"][veh["active"]] == 12.0)
        assert not np.array_equal(veh["pos"], plain[i]["veh"]["pos"])


def test_an_unknown_scenario_lists_the_known_ones():
    with pytest.raises(KeyError) as e:
        reference.scenario("no_such_road")
    for name in SCENARIOS:
        assert name in str(e.value)
    with pytest.raises(KeyError):
        reference.scenario("../reference")
