"""The NGSIM US-101 weave (``us101_weave``): routes, the weave's rules, and
agreement with the benchmark's plain reference (``bench/``).

- the program against ``bench/reference.py`` through the benchmark's own
  comparison (``run.run_cell`` on ``us101.period``) at a tiny CPU size:
  every compared number 0, ``route`` and ``exits_missed`` included; the
  bf16 control of that comparison fails on position;
- hand-built states: the off-ramp exit at the gore, a missed exit on each
  side, rightward moves across the lanes paced by the cooldown, the MOBIL
  veto;
- the other scenarios are untouched by the route: they draw none, count
  no missed exit, and a route in their state changes nothing.
"""

import copy
import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import SimConfig, init_state, sample_scenario_params
from repro.core.scenarios import list_scenarios
from repro.core.simulator import SimMetrics, _acc, sim_step

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "bench")
sys.path.insert(0, BENCH)

import control  # noqa: E402
import run  # noqa: E402

TINY = {"sweep": {"n_instances": 4, "chunk_steps": 50},
        "sim": {"n_slots": 48}, "devices": {"workers_per_chip": 2},
        "traffic": {"sample": 4, "fill_sim_seconds": 10}}
# the cell's geometry, at a few slots
CFG = SimConfig(n_slots=16, scenario="us101_weave", n_lanes=5,
                road_len=640.0, merge_start=60.0, merge_end=420.0,
                discharge_speed=4.0)
AUX, GORE, EXIT, THROUGH = 5, 420.0, 1, 0


def test_program_matches_the_reference():
    r = run.run_cell("us101.period", 2**31 + 29, 1.0, False, allow_cpu=True,
                     overrides=copy.deepcopy(TINY))
    assert r["correct"] is True, r["checks"]
    assert all(c["value"] == 0 for c in r["checks"].values()), r["checks"]


def test_the_bf16_control_fails_on_position():
    r = control.control("us101.period", 5, overrides=copy.deepcopy(TINY))
    assert r["correct"] is False
    gap = r["checks"]["pos_gap_m"]
    assert gap["value"] > gap["limit"], r["checks"]


def test_the_weave_is_exercised():
    """At the tiny size of the comparison above, routes of both kinds are
    drawn, and weave moves, lane changes and missed exits all happen."""
    cfg = dataclasses.replace(CFG, n_slots=48)
    keys = jax.random.split(jax.random.key(1), 4)
    sp = jax.vmap(lambda k: sample_scenario_params(k, cfg))(keys)
    st = jax.vmap(lambda k: init_state(cfg, k))(keys)
    m = jax.vmap(lambda _: SimMetrics.zeros())(keys)
    routes = np.zeros(2, int)

    @jax.jit
    def chunk(st, m):
        def body(c, _):
            s, mm = c
            s, d = jax.vmap(lambda s, p: sim_step(s, cfg, p))(s, sp)
            return (s, _acc(mm, d)), None
        return jax.lax.scan(body, (st, m), None, length=100)[0]

    for _ in range(20):
        st, m = chunk(st, m)
        act = np.asarray(st.active)
        routes += np.bincount(np.asarray(st.route)[act], minlength=2)
    assert routes.min() > 0
    for field in ("merges_ok", "lane_changes", "exits_missed", "throughput"):
        assert int(np.sum(getattr(m, field))) > 0, field


def _world(vehicles, cfg=CFG):
    """A state holding ``vehicles`` (dicts of pos, vel, lane, route and
    optionally cooldown) in the first slots, and no demand."""
    st = init_state(cfg, jax.random.key(0))
    n = len(vehicles)

    def col(k, default, dtype):
        vals = [v.get(k, default) for v in vehicles]
        return jnp.asarray(vals, dtype)

    st = st._replace(
        pos=st.pos.at[:n].set(col("pos", 0.0, jnp.float32)),
        vel=st.vel.at[:n].set(col("vel", 10.0, jnp.float32)),
        lane=st.lane.at[:n].set(col("lane", 0, jnp.int32)),
        route=st.route.at[:n].set(col("route", THROUGH, jnp.int32)),
        cooldown=st.cooldown.at[:n].set(col("cooldown", 0, jnp.int32)),
        active=st.active.at[:n].set(True),
    )
    sp = sample_scenario_params(jax.random.key(1), cfg)
    sp = sp._replace(lambda_main=jnp.zeros_like(sp.lambda_main),
                     lambda_ramp=jnp.zeros(()))
    return st, sp


STEP = jax.jit(lambda st, sp: sim_step(st, CFG, sp))


EXITS = [
    ("exit-bound takes the off-ramp",
     [dict(lane=AUX, route=EXIT, pos=GORE - 0.5)], 1, 0),
    ("exit-bound from lane 0 into the aux lane at the gore",
     [dict(lane=0, route=EXIT, pos=GORE - 0.5)], 1, 0),
    ("through in the aux lane, lane 0 blocked, takes the off-ramp",
     [dict(lane=AUX, route=THROUGH, pos=GORE - 0.5),
      dict(lane=0, route=THROUGH, pos=GORE + 2.0)], 1, 1),
    ("exit-bound on the mainline drives on past the gore",
     [dict(lane=2, route=EXIT, pos=GORE - 0.5)], 0, 0),
    ("exit-bound on the mainline leaves at the road end",
     [dict(lane=2, route=EXIT, pos=639.5)], 1, 1),
    ("through on the mainline leaves at the road end",
     [dict(lane=2, route=THROUGH, pos=639.5)], 1, 0),
]


@pytest.mark.parametrize("case,vehicles,exited,missed", EXITS,
                         ids=[c[0] for c in EXITS])
def test_exits_and_missed_exits(case, vehicles, exited, missed):
    st, sp = _world(vehicles)
    st2, d = STEP(st, sp)
    assert int(d.throughput) == exited, case
    assert int(d.exits_missed) == missed, case
    assert int(d.collisions) == 0, case
    if not exited:
        assert bool(st2.active[0]) and float(st2.pos[0]) > GORE, case


def test_rightward_moves_are_paced_by_the_cooldown():
    """An exit-bound vehicle entering the weave zone in lane 4 crosses to
    lane 0 one lane per cooldown (MOBIL, led by the lane-graded cap), then
    into the auxiliary lane once the cooldown is out (a forced move), and
    leaves by the off-ramp."""
    st, sp = _world([dict(lane=4, route=EXIT, pos=CFG.merge_start, vel=12.0)])
    lanes, m = [4], SimMetrics.zeros()
    for _ in range(600):
        st, d = STEP(st, sp)
        m = _acc(m, d)
        if not bool(st.active[0]):
            break
        lanes.append(int(st.lane[0]))
    assert int(m.throughput) == 1 and int(m.exits_missed) == 0
    assert int(m.collisions) == 0
    changes = [t for t in range(1, len(lanes)) if lanes[t] != lanes[t - 1]]
    assert [lanes[t] for t in changes] == [3, 2, 1, 0, AUX]
    assert int(m.lane_changes) == 4 and int(m.merges_ok) == 1
    assert min(np.diff(changes)) >= CFG.lane_change_cooldown


@pytest.mark.parametrize("route,moved", [(EXIT, False), (THROUGH, True)])
def test_no_mobil_move_away_from_the_exit(route, moved):
    """In the zone, a slow leader makes lane 2 pay; MOBIL moves a through
    vehicle there, never an exit-bound one."""
    st, sp = _world([dict(lane=1, route=route, pos=100.0, vel=15.0),
                     dict(lane=1, pos=115.0, vel=0.0),
                     dict(lane=0, pos=110.0, vel=0.0)])
    st = st._replace(v0=st.v0.at[1:3].set(0.1))
    st2, _ = STEP(st, sp)
    assert (int(st2.lane[0]) == 2) is moved


@pytest.mark.parametrize("name", [s for s in list_scenarios()
                                  if s != "us101_weave"])
def test_other_scenarios_ignore_the_route(name):
    """No route is drawn or counted, and a route in the state changes no
    other bit of a scenario without routes."""
    cfg = SimConfig(n_slots=16, scenario=name)
    sp = sample_scenario_params(jax.random.key(3), cfg)
    step = jax.jit(lambda s: sim_step(s, cfg, sp))
    plain = init_state(cfg, jax.random.key(4))
    tagged = plain._replace(route=jnp.ones_like(plain.route))
    m = SimMetrics.zeros()
    for _ in range(200):
        plain, d = step(plain)
        tagged, _ = step(tagged)
        m = _acc(m, d)
    assert int(np.asarray(plain.active).sum()) > 0
    assert not np.asarray(plain.route).any()
    assert int(m.exits_missed) == 0
    for f in plain._fields:
        if f not in ("route", "key"):
            np.testing.assert_array_equal(np.asarray(getattr(plain, f)),
                                          np.asarray(getattr(tagged, f)), f)
