"""Scenario API tests: merge parity oracle, per-scenario smoke + invariants.

The centerpiece is ``test_highway_merge_parity``: the pre-refactor
``sim_step`` (the seed implementation with the merge hardcoded, plus the
one declared spawn-headway bugfix) is frozen below as ``_legacy_sim_step``,
and the registry-dispatched ``highway_merge`` must reproduce its
trajectories **bit-for-bit** under every neighborhood-engine
implementation.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    SimConfig,
    get_scenario,
    init_state,
    list_scenarios,
    rollout,
    sample_scenario_params,
    sim_step,
)
from repro.core.neighbors import build_tables, query_lanes
from repro.core.scenario import ScenarioParams, driver_params
from repro.core.simulator import (
    INF,
    SimMetrics,
    SimState,
    _acc,
    idm_accel,
)

ALL_SCENARIOS = list_scenarios()


# ==========================================================================
# the parity oracle: the seed sim_step, frozen, with ONLY the declared
# spawn-headway fix (init speed clamps on the NEW driver's T) applied
# ==========================================================================

def _legacy_own_accel(st, cfg, query_lane, lead_idx, lead_gap, has_lead):
    v_lead = jnp.where(has_lead, st.vel[lead_idx], 0.0)
    gap = jnp.where(has_lead, lead_gap, INF)
    dv = jnp.where(has_lead, st.vel - v_lead, 0.0)
    a = idm_accel(st.vel, dv, gap, st.v0, st.T, st.a_max, st.b_comf, st.s0)
    on_ramp = query_lane == cfg.n_lanes
    wall_gap = cfg.merge_end - st.pos
    a_wall = idm_accel(
        st.vel, st.vel, wall_gap, st.v0, st.T, st.a_max, st.b_comf, st.s0
    )
    a = jnp.where(on_ramp, jnp.minimum(a, a_wall), a)
    return jnp.clip(a, -cfg.b_max, st.a_max)


def _legacy_mobil_candidate(st, cfg, a_now, own, tabs, cand_lane):
    nb = tabs.query(cand_lane)
    li, lg, hl, fi, fg, hf = nb
    a_new = _legacy_own_accel(st, cfg, cand_lane, li, lg, hl)

    a_j_before = jnp.where(hf, a_now[fi], 0.0)
    gap_j_after = jnp.where(hf, fg, INF)
    a_j_after = idm_accel(
        st.vel[fi], st.vel[fi] - st.vel, gap_j_after,
        st.v0[fi], st.T[fi], st.a_max[fi], st.b_comf[fi], st.s0[fi],
    )
    a_j_after = jnp.where(hf, a_j_after, 0.0)

    ki, hk = own.foll_idx, own.has_foll
    lead_pos = jnp.where(own.has_lead, st.pos[own.lead_idx], INF)
    lead_vel = jnp.where(own.has_lead, st.vel[own.lead_idx], 0.0)
    gap_k_after = (
        lead_pos[jnp.arange(st.pos.shape[0])] - st.pos[ki] - cfg.vehicle_len
    )
    a_k_before = jnp.where(hk, a_now[ki], 0.0)
    a_k_after = idm_accel(
        st.vel[ki], st.vel[ki] - lead_vel, gap_k_after,
        st.v0[ki], st.T[ki], st.a_max[ki], st.b_comf[ki], st.s0[ki],
    )
    a_k_after = jnp.where(hk, a_k_after, 0.0)

    incentive = (a_new - a_now) + st.politeness * (
        (a_j_after - a_j_before) + (a_k_after - a_k_before)
    )
    safe = (a_j_after >= -cfg.b_safe) & (
        jnp.where(hf, fg, INF) > 0.0
    ) & (jnp.where(hl, lg, INF) > 0.0)
    return incentive, safe


def _legacy_apply_lane_changes(st, cfg, a_now, own, tabs):
    on_main = (st.lane < cfg.n_lanes) & st.active
    can_change = on_main & (st.cooldown == 0)

    left = jnp.minimum(st.lane + 1, cfg.n_lanes - 1)
    right = jnp.maximum(st.lane - 1, 0)
    inc_l, safe_l = _legacy_mobil_candidate(st, cfg, a_now, own, tabs, left)
    inc_r, safe_r = _legacy_mobil_candidate(st, cfg, a_now, own, tabs, right)
    ok_l = safe_l & (inc_l > cfg.mobil_athr) & (left != st.lane) & can_change
    ok_r = safe_r & (inc_r > cfg.mobil_athr) & (right != st.lane) & can_change

    go_left = ok_l & (~ok_r | (inc_l >= inc_r))
    go_right = ok_r & ~go_left
    new_lane = jnp.where(go_left, left, jnp.where(go_right, right, st.lane))
    changed = go_left | go_right
    cooldown = jnp.where(
        changed, cfg.lane_change_cooldown, jnp.maximum(st.cooldown - 1, 0)
    )
    return new_lane, cooldown, jnp.sum(changed.astype(jnp.int32))


def _legacy_apply_ramp_merges(st, cfg, new_lane, tabs):
    on_ramp = (st.lane == cfg.n_lanes) & st.active
    in_zone = (st.pos >= cfg.merge_start) & (st.pos <= cfg.merge_end)
    zeros = jnp.zeros_like(st.lane)
    _, lg, hl, _, fg, hf = tabs.query(zeros)
    front_need = jnp.where(st.is_cav, 0.7, 1.0) * cfg.merge_gap_front
    rear_need = jnp.where(st.is_cav, 0.7, 1.0) * cfg.merge_gap_rear
    gap_ok = (
        (jnp.where(hl, lg, INF) > front_need)
        & (jnp.where(hf, fg, INF) > rear_need)
    )
    merge = on_ramp & in_zone & gap_ok
    merged_lane = jnp.where(merge, 0, new_lane)
    return merged_lane, jnp.sum(merge.astype(jnp.int32))


def _legacy_spawn(st, cfg, sp, key):
    n = st.pos.shape[0]
    n_spawn_lanes = cfg.n_lanes + 1
    lanes = jnp.arange(n_spawn_lanes)
    ku, kj = jax.random.split(key)
    u = jax.random.uniform(ku, (3, n_spawn_lanes))

    lam = jnp.concatenate([sp.lambda_main, sp.lambda_ramp[None]])
    arrive = u[0] < lam * cfg.dt
    in_lane = st.active[None, :] & (st.lane[None, :] == lanes[:, None])
    nearest = jnp.min(jnp.where(in_lane, st.pos[None, :], INF), axis=1)
    clear = nearest > cfg.spawn_gap

    free = ~st.active
    n_free = jnp.sum(free.astype(jnp.int32))
    want = arrive & clear
    rank = jnp.cumsum(want.astype(jnp.int32)) - want.astype(jnp.int32)
    ok = want & (rank < n_free)
    free_slots = jnp.argsort(~free, stable=True)
    slot = jnp.where(ok, free_slots[jnp.minimum(rank, n - 1)], n)

    cav = u[1] < sp.p_cav
    base_v0 = jnp.where(lanes == cfg.n_lanes, sp.v0_ramp, sp.v0_mean)
    new_v0 = base_v0 * (0.9 + 0.2 * u[2])
    dp = driver_params(cav, kj, n_spawn_lanes)
    # the declared satellite fix: clamp on the NEW driver's T, not the
    # claimed slot's stale previous-occupant T
    init_v = jnp.minimum(new_v0, nearest / jnp.maximum(dp["T"], 0.5))

    def put(arr, val):
        return arr.at[slot].set(val.astype(arr.dtype), mode="drop")

    st = st._replace(
        pos=put(st.pos, jnp.zeros_like(new_v0)),
        vel=put(st.vel, jnp.maximum(init_v * 0.8, 5.0)),
        lane=put(st.lane, lanes),
        active=put(st.active, jnp.ones_like(cav)),
        is_cav=put(st.is_cav, cav),
        v0=put(st.v0, new_v0),
        T=put(st.T, dp["T"]),
        a_max=put(st.a_max, dp["a_max"]),
        b_comf=put(st.b_comf, dp["b_comf"]),
        s0=put(st.s0, dp["s0"]),
        politeness=put(st.politeness, dp["politeness"]),
    )
    return st, jnp.sum(ok.astype(jnp.int32))


def _legacy_sim_step(st, cfg, sp):
    key, k_spawn = jax.random.split(st.key)
    st = st._replace(key=key)
    impl = cfg.neighbor_impl
    n_lanes_total = cfg.n_lanes + 1

    tabs = build_tables(
        st.pos, st.lane, st.active, cfg.vehicle_len, n_lanes_total, impl
    )
    own = tabs.query(st.lane)
    a_now = _legacy_own_accel(st, cfg, st.lane, own.lead_idx, own.lead_gap,
                              own.has_lead)

    new_lane, cooldown, n_lc = _legacy_apply_lane_changes(
        st, cfg, a_now, own, tabs
    )
    new_lane, n_merge = _legacy_apply_ramp_merges(st, cfg, new_lane, tabs)
    st = st._replace(lane=new_lane, cooldown=cooldown)

    nb = query_lanes(
        st.pos, st.lane, st.active, cfg.vehicle_len, st.lane, impl,
        n_lanes_total=n_lanes_total,
    )
    accel = _legacy_own_accel(st, cfg, st.lane, nb.lead_idx, nb.lead_gap,
                              nb.has_lead)
    accel = jnp.where(st.active, accel, 0.0)
    vel = jnp.maximum(st.vel + accel * cfg.dt, 0.0)
    pos = st.pos + vel * cfg.dt
    on_ramp = st.lane == cfg.n_lanes
    pos = jnp.where(on_ramp, jnp.minimum(pos, cfg.merge_end), pos)
    vel = jnp.where(on_ramp & (pos >= cfg.merge_end), 0.0, vel)
    st = st._replace(pos=pos, vel=vel)

    li2, hl2 = nb.lead_idx, nb.has_lead
    lg2 = jnp.where(
        hl2, st.pos[li2] - st.pos - cfg.vehicle_len, INF - cfg.vehicle_len
    )
    crashed = st.active & hl2 & (lg2 < 0.0)
    n_crash = jnp.sum(crashed.astype(jnp.int32))

    exited = st.active & (st.pos > cfg.road_len)
    n_out = jnp.sum(exited.astype(jnp.int32))
    active = st.active & ~exited & ~crashed
    st = st._replace(active=active, pos=jnp.where(active, st.pos, -INF))

    dv = jnp.where(hl2, st.vel - st.vel[li2], 0.0)
    ttc = jnp.where(
        st.active & hl2 & (dv > 0.1), jnp.maximum(lg2, 0.0) / dv, INF
    )
    min_ttc = jnp.min(ttc)

    blocked = (
        st.active & (st.lane == cfg.n_lanes)
        & (st.pos > cfg.merge_end - 10.0) & (st.vel < 0.5)
    )
    n_blocked = jnp.sum(blocked.astype(jnp.int32))

    st, n_spawn = _legacy_spawn(st, cfg, sp, k_spawn)
    st = st._replace(t=st.t + 1)

    delta = SimMetrics(
        throughput=n_out,
        spawned=n_spawn,
        speed_sum=jnp.sum(jnp.where(st.active, st.vel, 0.0)),
        speed_count=jnp.sum(st.active.astype(jnp.float32)),
        collisions=n_crash,
        merges_ok=n_merge,
        ramp_blocked_steps=n_blocked,
        lane_changes=n_lc,
        min_ttc=min_ttc,
        steps=jnp.ones((), jnp.int32),
        exits_missed=jnp.zeros((), jnp.int32),
    )
    return st, delta


def _leaves(tree):
    out = []
    for x in jax.tree.leaves(tree):
        if hasattr(x, "dtype") and jax.dtypes.issubdtype(
            x.dtype, jax.dtypes.prng_key
        ):
            x = jax.random.key_data(x)
        out.append(np.asarray(jax.device_get(x)))
    return out


# ==========================================================================
# parity
# ==========================================================================

@pytest.mark.parametrize(
    "impl,steps",
    [("reference", 250), ("dense", 250), ("sort", 250), ("pallas", 40)],
)
def test_highway_merge_parity(impl, steps):
    """Registry-dispatched highway_merge == the frozen seed step, bitwise."""
    cfg = SimConfig(n_slots=24, scenario="highway_merge", neighbor_impl=impl)
    sp = sample_scenario_params(jax.random.key(1), cfg)
    st_old = init_state(cfg, jax.random.key(0))
    st_new = init_state(cfg, jax.random.key(0))
    m_old, m_new = SimMetrics.zeros(), SimMetrics.zeros()
    step_old = jax.jit(lambda s: _legacy_sim_step(s, cfg, sp))
    step_new = jax.jit(lambda s: sim_step(s, cfg, sp))
    acc = jax.jit(_acc)
    for _ in range(steps):
        st_old, d_old = step_old(st_old)
        st_new, d_new = step_new(st_new)
        m_old, m_new = acc(m_old, d_old), acc(m_new, d_new)
    for a, b in zip(_leaves(st_old), _leaves(st_new)):
        np.testing.assert_array_equal(a, b)
    for a, b in zip(_leaves(m_old), _leaves(m_new)):
        np.testing.assert_array_equal(a, b)


def test_legacy_oracle_exercises_merge():
    """The parity run is meaningful: traffic actually spawns, merges and
    exits in the legacy oracle at the parity horizon."""
    cfg = SimConfig(n_slots=24, scenario="highway_merge")
    sp = sample_scenario_params(jax.random.key(1), cfg)
    st = init_state(cfg, jax.random.key(0))
    m = SimMetrics.zeros()
    step = jax.jit(lambda s: _legacy_sim_step(s, cfg, sp))
    acc = jax.jit(_acc)
    for _ in range(400):
        st, d = step(st)
        m = acc(m, d)
    assert int(m.spawned) > 10
    assert int(m.merges_ok) > 0
    assert int(m.throughput) > 0


# ==========================================================================
# per-scenario smoke + invariants
# ==========================================================================

@pytest.mark.parametrize("name", ALL_SCENARIOS)
def test_scenario_smoke(name):
    cfg = SimConfig(n_slots=16, scenario=name)
    sp = sample_scenario_params(jax.random.key(2), cfg)
    m = rollout(jax.random.key(3), cfg, sp, 300)
    assert int(m.steps) == 300
    assert int(m.spawned) > 0
    assert float(m.speed_sum) > 0.0
    for leaf in jax.tree.leaves(m):
        assert np.all(np.isfinite(np.asarray(leaf)))


@pytest.mark.parametrize("name", ALL_SCENARIOS)
def test_scenario_conservation_and_bounds(name):
    """spawned == exited + crashed + still-active; lanes/positions legal."""
    cfg = SimConfig(n_slots=16, scenario=name)
    geom = get_scenario(name).geometry(cfg)
    sp = sample_scenario_params(jax.random.key(5), cfg)
    st = init_state(cfg, jax.random.key(6))
    m = SimMetrics.zeros()
    step = jax.jit(lambda s: sim_step(s, cfg, sp))
    acc = jax.jit(_acc)
    for _ in range(250):
        st, d = step(st)
        m = acc(m, d)
    active_now = int(np.asarray(st.active).sum())
    assert (
        int(m.spawned)
        == int(m.throughput) + int(m.collisions) + active_now
    )
    act = np.asarray(st.active)
    lane = np.asarray(st.lane)[act]
    pos = np.asarray(st.pos)[act]
    assert np.all((lane >= 0) & (lane < geom.n_lanes_total))
    if geom.ring:
        assert np.all((pos >= 0.0) & (pos <= geom.road_len))
    else:
        assert np.all(pos <= geom.road_len + 1.0)


@pytest.mark.parametrize("name", ALL_SCENARIOS)
def test_scenario_deterministic(name):
    cfg = SimConfig(n_slots=16, scenario=name)
    sp = sample_scenario_params(jax.random.key(7), cfg)
    m1 = rollout(jax.random.key(8), cfg, sp, 150)
    m2 = rollout(jax.random.key(8), cfg, sp, 150)
    for a, b in zip(jax.tree.leaves(m1), jax.tree.leaves(m2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_scenarios_actually_differ():
    """Same seeds, different scenarios → different trajectories (the hooks
    are live, not decorative)."""
    outs = []
    for name in ALL_SCENARIOS:
        cfg = SimConfig(n_slots=16, scenario=name)
        sp = sample_scenario_params(jax.random.key(9), cfg)
        outs.append(rollout(jax.random.key(10), cfg, sp, 200))
    sigs = [
        tuple(float(np.asarray(x)) for x in jax.tree.leaves(m))
        for m in outs
    ]
    assert len(set(sigs)) == len(sigs)


@pytest.mark.parametrize("name", ALL_SCENARIOS)
def test_no_spawn_into_occupied_headway(name):
    """No collisions-from-spawn: a blocker parked inside spawn_gap on every
    lane suppresses arrivals entirely."""
    cfg = SimConfig(n_slots=32, scenario=name)
    geom = get_scenario(name).geometry(cfg)
    sp = sample_scenario_params(jax.random.key(11), cfg)
    # demand cranked to 1: every lane wants to spawn every step
    sp = sp._replace(
        lambda_main=jnp.ones_like(sp.lambda_main) * 10.0,
        lambda_ramp=jnp.asarray(10.0),
    )
    st = init_state(cfg, jax.random.key(12))
    n_block = geom.n_lanes_total
    idx = jnp.arange(n_block)
    st = st._replace(
        pos=st.pos.at[idx].set(cfg.spawn_gap * 0.5),
        vel=st.vel.at[idx].set(0.0),
        v0=st.v0.at[idx].set(0.1),       # parked: blockers never move
        lane=st.lane.at[idx].set(idx.astype(st.lane.dtype)),
        active=st.active.at[idx].set(True),
        cooldown=st.cooldown.at[idx].set(10_000),  # and never lane-change
    )
    step = jax.jit(lambda s: sim_step(s, cfg, sp))
    for _ in range(5):
        st, d = step(st)
        assert int(d.spawned) == 0
    assert int(np.asarray(st.active).sum()) == n_block


# ==========================================================================
# the spawn-headway satellite fix: init speed must use the NEW driver's T
# ==========================================================================

def test_spawn_init_speed_uses_fresh_T():
    """A stale, huge T left in a free slot by a previous occupant must not
    throttle the next spawn's entry speed (regression for the st.T[slot]
    read-before-write bug).

    The headway clamp only binds when `nearest` is finite, so park one
    blocker per spawn lane at a moderate distance: with the bug, init_v =
    nearest/stale_T ~ 0 and every spawn enters at the 5 m/s floor; with the
    fix it enters near nearest/T_fresh (>> 5 m/s)."""
    cfg = SimConfig(n_slots=16, scenario="highway_merge")
    sp = sample_scenario_params(jax.random.key(13), cfg)
    sp = sp._replace(
        lambda_main=jnp.ones_like(sp.lambda_main) * 10.0,  # spawn now
        lambda_ramp=jnp.asarray(10.0),
        p_cav=jnp.asarray(0.0),
    )
    st = init_state(cfg, jax.random.key(14))
    n_block = cfg.n_lanes + 1
    idx = jnp.arange(n_block)
    st = st._replace(
        # parked blockers 40 m downstream of the spawn point in every lane
        pos=st.pos.at[idx].set(40.0),
        vel=st.vel.at[idx].set(0.0),
        v0=st.v0.at[idx].set(0.1),
        lane=st.lane.at[idx].set(idx.astype(st.lane.dtype)),
        active=st.active.at[idx].set(True),
        cooldown=st.cooldown.at[idx].set(10_000),
        # stale garbage T everywhere, including the free slots about to be
        # claimed — the buggy read picks this up, the fixed one never sees it
        T=jnp.full_like(st.T, 1e6),
    )
    st, d = jax.jit(lambda s: sim_step(s, cfg, sp))(st)
    assert int(d.spawned) >= 1
    spawned_mask = np.array(st.active)     # writable copy
    spawned_mask[np.asarray(idx)] = False  # drop the blockers
    vel = np.asarray(st.vel)[spawned_mask]
    # fresh human T ~ 1.3-1.7 → init_v ~ 40/T, entry vel = 0.8*init_v > 15;
    # the stale-T bug floors every entry at 5.0 m/s
    assert vel.min() > 10.0
    T = np.asarray(st.T)[spawned_mask]
    assert T.max() < 100.0  # the written T is the freshly drawn one
