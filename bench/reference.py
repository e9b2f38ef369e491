"""Plain reference of the traffic microsimulation the sweep runs.

Written from the model's equations, not from the program: it imports
nothing of ``repro``. Given a deployment's configuration (the JSON file
under ``bench/configs``) it rebuilds one instance from the sweep seed and
its instance id, steps it one vehicle step at a time, and records what the
sweep's recorder records:

- demand: per-lane Bernoulli arrivals of rate ``lambda * dt`` at the road
  start, admitted when the nearest vehicle in the lane is past
  ``spawn_gap`` (on a ring also when the seam has room), each arrival
  taking the lowest free slot in lane order, with human or CAV driver
  parameters (``driver_tables``), humans jittered by U(0.85, 1.15);
- car following: the Intelligent Driver Model against the nearest
  vehicle strictly ahead in the lane (an all-pairs search per lane);
- lane changes: MOBIL (incentive with politeness, safety ``b_safe``,
  threshold ``mobil_athr``, cooldown), then the scenario's forced moves;
- scenarios: the highway on-ramp merge, the lane drop, the ring road with
  a periodic braking pulse, and the work-zone speed limit;
- collisions (overlap with the followed leader removes the follower),
  exits past the road end, time to collision over closing pairs.

``dtype`` is the precision of every real-valued state, parameter and
accumulator: ``float32`` as the configuration states, ``bfloat16`` for the
control that a comparison has to fail.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

INF = 1e9


class Road(NamedTuple):
    n_lanes: int        # main lanes
    n_total: int        # lane-table rows (main lanes + ramp)
    length: float
    zone_start: float
    zone_end: float
    ring: bool


def road(sim: dict, scenario: str) -> Road:
    n, length = sim["n_lanes"], sim["road_len"]
    zs, ze = sim["merge_start"], sim["merge_end"]
    if scenario == "highway_merge":
        return Road(n, n + 1, length, zs, ze, False)
    if scenario == "stop_and_go":
        ring = min(length, max(sim["n_slots"], 8) * 30.0 / n)
        return Road(n, n, ring, 0.0, 0.0, True)
    return Road(n, n, length, zs, ze, False)


# ---------------------------------------------------------------------------
# instance draws
# ---------------------------------------------------------------------------

def sample_params(key, sim: dict, scenario: str):
    """(lambda_main[n], lambda_ramp, p_cav, v0_mean, v0_ramp, aux0, aux1)."""
    n = sim["n_lanes"]
    z = jnp.zeros(())
    u = jax.random.uniform
    if scenario == "highway_merge":
        k1, k2, k3, k4, _ = jax.random.split(key, 5)
        v0 = u(k4, (), minval=26.0, maxval=33.0)
        return (u(k1, (n,), minval=0.15, maxval=0.55),
                u(k2, (), minval=0.05, maxval=0.30),
                u(k3, (), minval=0.0, maxval=1.0), v0, v0 * 0.7, z, z)
    if scenario == "lane_drop":
        k1, k2, k3, _ = jax.random.split(key, 4)
        v0 = u(k3, (), minval=26.0, maxval=33.0)
        return (u(k1, (n,), minval=0.25, maxval=0.65), z,
                u(k2, (), minval=0.0, maxval=1.0), v0, v0, z, z)
    if scenario == "stop_and_go":
        k1, k2, k3, _, k5, k6 = jax.random.split(key, 6)
        v0 = u(k3, (), minval=26.0, maxval=33.0)
        return (u(k1, (n,), minval=0.25, maxval=0.70), z,
                u(k2, (), minval=0.0, maxval=1.0), v0, v0,
                u(k5, (), minval=2.0, maxval=5.0),
                u(k6, (), minval=20.0, maxval=45.0))
    if scenario == "speed_limit_zone":
        k1, k2, k3, _, k5 = jax.random.split(key, 5)
        v0 = u(k3, (), minval=26.0, maxval=33.0)
        return (u(k1, (n,), minval=0.15, maxval=0.55), z,
                u(k2, (), minval=0.0, maxval=1.0), v0, v0,
                u(k5, (), minval=10.0, maxval=18.0), z)
    raise KeyError(f"the reference has no scenario {scenario!r}")


class Veh(NamedTuple):
    pos: jax.Array
    vel: jax.Array
    lane: jax.Array
    active: jax.Array
    is_cav: jax.Array
    v0: jax.Array
    T: jax.Array
    a_max: jax.Array
    b_comf: jax.Array
    s0: jax.Array
    polite: jax.Array
    cooldown: jax.Array
    key: jax.Array
    t: jax.Array


class Counters(NamedTuple):
    throughput: jax.Array
    spawned: jax.Array
    speed_sum: jax.Array
    speed_count: jax.Array
    collisions: jax.Array
    merges_ok: jax.Array
    blocked: jax.Array
    lane_changes: jax.Array
    min_ttc: jax.Array
    steps: jax.Array


def instance(seed: int, i: int, cfg: dict, dtype=jnp.float32):
    """Scenario name, parameters, empty world, counters and horizon of
    instance ``i`` of the sweep with seed ``seed``."""
    sim, sweep = cfg["sim"], cfg["sweep"]
    roster = cfg["roster"]
    scenario = roster[i % len(roster)]
    k = jax.random.fold_in(jax.random.key(seed), i)
    params = sample_params(jax.random.fold_in(k, 1), sim, scenario)
    params = tuple(jnp.asarray(p, dtype) for p in params)
    n = sim["n_slots"]
    h = cfg["driver_tables"]["human"]
    zf = jnp.zeros((n,), dtype)
    veh = Veh(zf - INF, zf, jnp.zeros((n,), jnp.int32), jnp.zeros((n,), bool),
              jnp.zeros((n,), bool), zf + 30.0, zf + h["T"], zf + h["a_max"],
              zf + h["b_comf"], zf + h["s0"], zf + h["politeness"],
              jnp.zeros((n,), jnp.int32), jax.random.fold_in(k, 2),
              jnp.zeros((), jnp.int32))
    zi, z = jnp.zeros((), jnp.int32), jnp.zeros((), dtype)
    counters = Counters(zi, zi, z, z, zi, zi, zi, zi, jnp.asarray(INF, dtype),
                        zi)
    steps = sweep["steps_per_instance"]
    if sweep["vary_horizon"]:
        frac = jax.random.uniform(jax.random.fold_in(k, 3), (),
                                  minval=sweep["min_horizon_frac"],
                                  maxval=1.0)
        horizon = int((frac * steps).astype(jnp.int32))
    else:
        horizon = steps
    return scenario, params, veh, counters, horizon


# ---------------------------------------------------------------------------
# physics
# ---------------------------------------------------------------------------

def idm(v, dv, gap, v0, T, a_max, b_comf, s0):
    gap = jnp.maximum(gap, 0.1)
    s_star = s0 + jnp.maximum(
        0.0, v * T + v * dv / (2.0 * jnp.sqrt(a_max * b_comf)))
    free = (v / jnp.maximum(v0, 0.1)) ** 4
    return a_max * (1.0 - free - (s_star / gap) ** 2)


def neighbours(pos, lane, active, veh_len, qlane):
    """Nearest vehicle strictly ahead / behind each vehicle in lane
    ``qlane[i]``; ties go to the lowest slot; an inactive vehicle has none.
    Returns (lead, lead_gap, has_lead, foll, foll_gap, has_foll)."""
    d = pos[None, :] - pos[:, None]
    n = pos.shape[0]
    ok = ((lane[None, :] == qlane[:, None]) & active[None, :]
          & active[:, None] & ~jnp.eye(n, dtype=bool))
    ahead, behind = ok & (d > 0.0), ok & (d < 0.0)
    ld = jnp.where(ahead, d, INF)
    fd = jnp.where(behind, -d, INF)
    return (jnp.argmin(ld, axis=1), jnp.min(ld, axis=1) - veh_len,
            jnp.any(ahead, axis=1), jnp.argmin(fd, axis=1),
            jnp.min(fd, axis=1) - veh_len, jnp.any(behind, axis=1))


def lane_tables(v: Veh, veh_len, n_total):
    return jax.vmap(
        lambda l: neighbours(v.pos, v.lane, v.active, veh_len,
                             jnp.full_like(v.lane, l))
    )(jnp.arange(n_total))


def ask(tables, qlane):
    cols = jnp.arange(qlane.shape[0])
    return tuple(t[qlane, cols] for t in tables)


def wall(v: Veh, wall_pos, on, a):
    a_wall = idm(v.vel, v.vel, wall_pos - v.pos, v.v0, v.T, v.a_max,
                 v.b_comf, v.s0)
    return jnp.where(on, jnp.minimum(a, a_wall), a)


def ring_rear(v: Veh, rd: Road):
    ls = jnp.arange(rd.n_lanes)
    keyed = jnp.where(v.active[None, :] & (v.lane[None, :] == ls[:, None]),
                      v.pos[None, :], INF)
    return jnp.min(keyed, axis=1), v.vel[jnp.argmin(keyed, axis=1)]


def accel(v: Veh, sim, rd: Road, scenario, p, qlane, nb, rear):
    """IDM against the lead in ``qlane``, the scenario's extra limits, and
    the clamp to [-b_max, a_max]."""
    lead, lgap, has_lead = nb[0], nb[1], nb[2]
    v_lead = jnp.where(has_lead, v.vel[lead], 0.0)
    gap = jnp.where(has_lead, lgap, INF)
    dv = jnp.where(has_lead, v.vel - v_lead, 0.0)
    a = idm(v.vel, dv, gap, v.v0, v.T, v.a_max, v.b_comf, v.s0)
    if scenario == "highway_merge":
        a = wall(v, rd.zone_end, qlane == rd.n_lanes, a)
    elif scenario == "lane_drop":
        a = wall(v, rd.zone_end, qlane == 0, a)
    elif scenario == "stop_and_go":
        rear_pos, rear_vel = rear
        q = jnp.clip(qlane, 0, rd.n_lanes - 1)
        a_wrap = idm(v.vel, v.vel - rear_vel[q],
                     rear_pos[q] + rd.length - v.pos - sim["vehicle_len"],
                     v.v0, v.T, v.a_max, v.b_comf, v.s0)
        a = jnp.where(~has_lead & (rear_pos[q] < INF * 0.5),
                      jnp.minimum(a, a_wrap), a)
        phase = jnp.mod(v.t.astype(jnp.float32) * sim["dt"],
                        jnp.maximum(p[6], 1.0))
        band = (v.pos >= 0.45 * rd.length) & (v.pos <= 0.55 * rd.length)
        a = jnp.where((phase < 5.0) & band, jnp.minimum(a, -p[5]), a)
    elif scenario == "speed_limit_zone":
        limit = jnp.maximum(p[5], 0.1)
        zone = (v.pos >= rd.zone_start) & (v.pos <= rd.zone_end)
        a = jnp.where(zone, jnp.minimum(a, v.a_max * (1.0 - (v.vel / limit) ** 4)), a)
        a_in = idm(v.vel, v.vel - limit, rd.zone_start - v.pos, v.v0, v.T,
                   v.a_max, v.b_comf, v.s0)
        a = jnp.where((v.pos < rd.zone_start) & (v.vel > limit),
                      jnp.minimum(a, a_in), a)
    return jnp.clip(a, -sim["b_max"], v.a_max)


def mobil(v: Veh, sim, rd, scenario, p, a_now, own, tables, cand, rear):
    nb = ask(tables, cand)
    _, lg, hl, fi, fg, hf = nb
    a_new = accel(v, sim, rd, scenario, p, cand, nb, rear)
    a_j0 = jnp.where(hf, a_now[fi], 0.0)
    a_j1 = jnp.where(hf, idm(v.vel[fi], v.vel[fi] - v.vel,
                             jnp.where(hf, fg, INF), v.v0[fi], v.T[fi],
                             v.a_max[fi], v.b_comf[fi], v.s0[fi]), 0.0)
    lead, _, has_lead, ki, _, hk = own
    lead_pos = jnp.where(has_lead, v.pos[lead], INF)
    lead_vel = jnp.where(has_lead, v.vel[lead], 0.0)
    a_k0 = jnp.where(hk, a_now[ki], 0.0)
    a_k1 = jnp.where(hk, idm(v.vel[ki], v.vel[ki] - lead_vel,
                             lead_pos - v.pos[ki] - sim["vehicle_len"],
                             v.v0[ki], v.T[ki], v.a_max[ki], v.b_comf[ki],
                             v.s0[ki]), 0.0)
    gain = (a_new - a_now) + v.polite * ((a_j1 - a_j0) + (a_k1 - a_k0))
    safe = ((a_j1 >= -sim["b_safe"]) & (jnp.where(hf, fg, INF) > 0.0)
            & (jnp.where(hl, lg, INF) > 0.0))
    return gain, safe


def gaps_ok(v: Veh, sim, tables, target):
    _, lg, hl, _, fg, hf = ask(tables, target)
    scale = jnp.where(v.is_cav, 0.7, 1.0)
    return ((jnp.where(hl, lg, INF) > scale * sim["merge_gap_front"])
            & (jnp.where(hf, fg, INF) > scale * sim["merge_gap_rear"]))


def step(v: Veh, m: Counters, p, sim: dict, scenario: str, drivers: dict):
    rd = road(sim, scenario)
    vl = sim["vehicle_len"]
    key, k_spawn = jax.random.split(v.key)
    v = v._replace(key=key)

    # lane changes on the pre-move snapshot
    tables = lane_tables(v, vl, rd.n_total)
    rear = ring_rear(v, rd) if rd.ring else None
    own = ask(tables, v.lane)
    a_now = accel(v, sim, rd, scenario, p, v.lane, own, rear)
    may = v.lane < rd.n_lanes
    if rd.ring:
        may = may & (v.pos > 0.1 * rd.length) & (v.pos < 0.9 * rd.length)
    may = may & v.active & (v.cooldown == 0)
    left = jnp.minimum(v.lane + 1, rd.n_lanes - 1)
    right = jnp.maximum(v.lane - 1, 0)

    def allowed(cand):
        if scenario != "lane_drop":
            return True
        return ~((cand == 0) & (v.lane != 0) & (v.pos >= rd.zone_start))

    g_l, s_l = mobil(v, sim, rd, scenario, p, a_now, own, tables, left, rear)
    g_r, s_r = mobil(v, sim, rd, scenario, p, a_now, own, tables, right, rear)
    ok_l = (s_l & (g_l > sim["mobil_athr"]) & (left != v.lane) & may
            & allowed(left))
    ok_r = (s_r & (g_r > sim["mobil_athr"]) & (right != v.lane) & may
            & allowed(right))
    go_l = ok_l & (~ok_r | (g_l >= g_r))
    go_r = ok_r & ~go_l
    lane = jnp.where(go_l, left, jnp.where(go_r, right, v.lane))
    changed = go_l | go_r
    cooldown = jnp.where(changed, sim["lane_change_cooldown"],
                         jnp.maximum(v.cooldown - 1, 0))
    n_lc = jnp.sum(changed.astype(jnp.int32))

    n_forced = jnp.zeros((), jnp.int32)
    zone = (v.pos >= rd.zone_start) & (v.pos <= rd.zone_end)
    if scenario == "highway_merge":
        move = ((v.lane == rd.n_lanes) & v.active & zone
                & gaps_ok(v, sim, tables, jnp.zeros_like(v.lane)))
        lane, n_forced = jnp.where(move, 0, lane), jnp.sum(move.astype(jnp.int32))
    elif scenario == "lane_drop":
        move = ((v.lane == 0) & v.active & zone
                & gaps_ok(v, sim, tables, jnp.full_like(v.lane, 1)))
        lane, n_forced = jnp.where(move, 1, lane), jnp.sum(move.astype(jnp.int32))
    v = v._replace(lane=lane, cooldown=cooldown)

    # move on the post-change snapshot
    nb = neighbours(v.pos, v.lane, v.active, vl, v.lane)
    rear = ring_rear(v, rd) if rd.ring else None
    a = jnp.where(v.active, accel(v, sim, rd, scenario, p, v.lane, nb, rear),
                  0.0)
    vel = jnp.maximum(v.vel + a * sim["dt"], 0.0)
    pos = v.pos + vel * sim["dt"]
    end = None
    if scenario == "highway_merge":
        end = v.lane == rd.n_lanes
    elif scenario == "lane_drop":
        end = v.lane == 0
    if end is not None:
        pos = jnp.where(end, jnp.minimum(pos, rd.zone_end), pos)
        vel = jnp.where(end & (pos >= rd.zone_end), 0.0, vel)
    if rd.ring:
        pos = jnp.where(v.active, jnp.mod(pos, rd.length), pos)
    v = v._replace(pos=pos, vel=vel)

    # collisions with the followed leader, exits, time to collision
    lead, has_lead = nb[0], nb[2]
    d = v.pos[lead] - v.pos
    if rd.ring:
        d = jnp.mod(d + 0.5 * rd.length, rd.length) - 0.5 * rd.length
    lgap = jnp.where(has_lead, d - vl, INF - vl)
    crashed = v.active & has_lead & (lgap < 0.0)
    exited = (jnp.zeros_like(v.active) if rd.ring
              else v.active & (v.pos > rd.length))
    active = v.active & ~exited & ~crashed
    v = v._replace(active=active, pos=jnp.where(active, v.pos, -INF))
    dv = jnp.where(has_lead, v.vel - v.vel[lead], 0.0)
    ttc = jnp.where(v.active & has_lead & (dv > 0.1),
                    jnp.maximum(lgap, 0.0) / dv, INF)

    if scenario == "stop_and_go":
        blocked = v.active & (v.vel < 2.0)
    elif scenario == "speed_limit_zone":
        blocked = (v.active & (v.pos >= rd.zone_start)
                   & (v.pos <= rd.zone_end))
    elif end is not None:
        blocked = (v.active & end & (v.pos > rd.zone_end - 10.0)
                   & (v.vel < 0.5))
    else:
        blocked = jnp.zeros_like(v.active)

    v, n_spawn = spawn(v, p, sim, rd, scenario, drivers, k_spawn)
    v = v._replace(t=v.t + 1)
    m = Counters(
        m.throughput + jnp.sum(exited.astype(jnp.int32)),
        m.spawned + n_spawn,
        m.speed_sum + jnp.sum(jnp.where(v.active, v.vel, 0.0)),
        m.speed_count + jnp.sum(v.active.astype(m.speed_count.dtype)),
        m.collisions + jnp.sum(crashed.astype(jnp.int32)),
        m.merges_ok + n_forced,
        m.blocked + jnp.sum(blocked.astype(jnp.int32)),
        m.lane_changes + n_lc,
        jnp.minimum(m.min_ttc, jnp.min(ttc)),
        m.steps + 1,
    )
    return v, m


def spawn(v: Veh, p, sim, rd: Road, scenario, drivers, key):
    n = v.pos.shape[0]
    lam_main, lam_ramp, p_cav, v0_mean, v0_ramp = p[:5]
    if scenario == "highway_merge":
        lanes = jnp.arange(rd.n_lanes + 1)
        lam = jnp.concatenate([lam_main, lam_ramp[None]])
        base_v0 = jnp.where(lanes == rd.n_lanes, v0_ramp, v0_mean)
    else:
        lanes = jnp.arange(rd.n_lanes)
        lam = lam_main
        base_v0 = jnp.full((rd.n_lanes,), 1.0) * v0_mean
    n_l = lanes.shape[0]
    ku, kj = jax.random.split(key)
    u = jax.random.uniform(ku, (3, n_l))
    arrive = u[0] < lam * sim["dt"]
    in_lane = v.active[None, :] & (v.lane[None, :] == lanes[:, None])
    nearest = jnp.min(jnp.where(in_lane, v.pos[None, :], INF), axis=1)
    clear = nearest > sim["spawn_gap"]
    if rd.ring:
        rear_gap = rd.length - jnp.max(
            jnp.where(in_lane, v.pos[None, :], -INF), axis=1)
        clear = clear & (rear_gap > 3.0 * sim["spawn_gap"])
    free = ~v.active
    want = arrive & clear
    rank = jnp.cumsum(want.astype(jnp.int32)) - want.astype(jnp.int32)
    ok = want & (rank < jnp.sum(free.astype(jnp.int32)))
    order = jnp.argsort(~free, stable=True)
    slot = jnp.where(ok, order[jnp.minimum(rank, n - 1)], n)

    cav = u[1] < p_cav
    new_v0 = base_v0 * (0.9 + 0.2 * u[2])
    jit = jax.random.uniform(kj, (n_l,), minval=0.85, maxval=1.15)
    h, c = drivers["human"], drivers["cav"]

    def draw(name):
        base = jnp.where(cav, c[name], h[name])
        return jnp.where(cav, base, base * jit)

    T = draw("T")
    init_v = jnp.minimum(new_v0, nearest / jnp.maximum(T, 0.5))

    def put(arr, val):
        return arr.at[slot].set(val.astype(arr.dtype), mode="drop")

    v = v._replace(
        pos=put(v.pos, jnp.zeros_like(new_v0)),
        vel=put(v.vel, jnp.maximum(init_v * 0.8, 5.0)),
        lane=put(v.lane, lanes),
        active=put(v.active, jnp.ones_like(cav)),
        is_cav=put(v.is_cav, cav),
        v0=put(v.v0, new_v0),
        T=put(v.T, T),
        a_max=put(v.a_max, draw("a_max")),
        b_comf=put(v.b_comf, draw("b_comf")),
        s0=put(v.s0, draw("s0")),
        polite=put(v.polite, jnp.where(cav, c["politeness"],
                                       h["politeness"])),
    )
    return v, jnp.sum(ok.astype(jnp.int32))


# ---------------------------------------------------------------------------
# recording and whole rollouts
# ---------------------------------------------------------------------------

CHANNELS = ("mean_speed", "active_count", "throughput", "lane_changes",
            "collisions", "min_ttc")


def snapshot(v: Veh, m: Counters, k_slots: int):
    """One recorded row: the channels, then the first ``k_slots`` slots'
    lane, speed and activity."""
    count = jnp.sum(v.active.astype(jnp.float32))
    series = jnp.stack([
        jnp.sum(jnp.where(v.active, v.vel, 0.0)).astype(jnp.float32)
        / jnp.maximum(count, 1.0),
        count,
        m.throughput.astype(jnp.float32),
        m.lane_changes.astype(jnp.float32),
        m.collisions.astype(jnp.float32),
        m.min_ttc.astype(jnp.float32),
    ])
    return (series, v.lane[:k_slots], v.vel[:k_slots].astype(jnp.float32),
            v.active[:k_slots])


@functools.partial(jax.jit, static_argnames=("sim", "scenario", "drivers",
                                             "n_steps", "every", "k_slots"))
def _block(v, m, p, target, *, sim, scenario, drivers, n_steps, every,
           k_slots):
    """``n_steps`` steps of a batch of instances, each frozen once it
    reaches its own ``target``; one recorded row per ``every`` steps."""
    sim, drivers = dict(sim), {k: dict(d) for k, d in drivers}

    def one(v, m, p, target):
        def body(carry, _):
            v, m = carry
            v2, m2 = step(v, m, p, sim, scenario, drivers)
            live = v.t < target
            v = jax.tree.map(lambda a, b: jnp.where(live, b, a), v, v2)
            m = jax.tree.map(lambda a, b: jnp.where(live, b, a), m, m2)
            return (v, m), None

        def window(carry, _):
            carry, _ = jax.lax.scan(body, carry, None, length=every)
            return carry, snapshot(*carry, k_slots)

        (v, m), rows = jax.lax.scan(window, (v, m), None,
                                    length=n_steps // every)
        return v, m, rows

    return jax.vmap(one)(v, m, p, target)


def _freeze(d: dict):
    return tuple(sorted(d.items()))


def rollout(cfg: dict, seed: int, ids, targets, dtype=jnp.float32,
            block: int = 100):
    """Step instances ``ids`` of sweep ``seed`` to their own step counts
    ``targets`` (each capped by its horizon). Returns, per instance, a dict
    of numpy arrays: the final vehicle state, the counters, the horizon and
    every recorded row up to the instance's step count. Instances run in
    batches of one scenario, ``block`` steps per call, every batch padded
    to ``len(ids)`` rows, so each scenario compiles one program."""
    import numpy as np

    sim = cfg["sim"]
    every = cfg["record"]["record_every"]
    k_slots = cfg["record"]["k_slots"]
    block = -(-block // every) * every
    drivers = tuple((k, _freeze(d)) for k, d in cfg["driver_tables"].items())
    made = [instance(seed, int(i), cfg, dtype) for i in ids]
    out: dict[int, dict] = {}
    by_scenario: dict[str, list[int]] = {}
    for j, (scenario, *_rest) in enumerate(made):
        by_scenario.setdefault(scenario, []).append(j)
    for scenario, js in by_scenario.items():
        rows_of = js + [js[0]] * (len(made) - len(js))     # padded batch
        stack = lambda xs: jax.tree.map(lambda *a: jnp.stack(a), *xs)  # noqa: E731
        p = stack([made[j][1] for j in rows_of])
        v = stack([made[j][2] for j in rows_of])
        m = stack([made[j][3] for j in rows_of])
        ends = [min(int(targets[j]), made[j][4]) for j in rows_of]
        target = jnp.asarray(ends)
        run = functools.partial(
            _block, sim=_freeze(sim), scenario=scenario, drivers=drivers,
            n_steps=block, every=every, k_slots=k_slots)
        parts = []
        for _ in range(-(-max(ends) // block)):
            v, m, rows = run(v, m, p, target)
            parts.append(jax.device_get(rows))
        v, m = jax.device_get((v, m))
        rows = [np.concatenate([part[c] for part in parts], axis=1)
                for c in range(4)]
        for r, j in enumerate(js):
            n_rows = ends[r] // every
            out[int(ids[j])] = {
                "scenario": scenario,
                "horizon": made[j][4],
                "t": ends[r],
                "veh": {f: np.asarray(x[r]) for f, x in zip(Veh._fields, v)
                        if f != "key"},
                "counters": {f: np.asarray(x[r])
                             for f, x in zip(Counters._fields, m)},
                "series": np.asarray(rows[0][r][:n_rows]),
                "lane": np.asarray(rows[1][r][:n_rows]),
                "speed": np.asarray(rows[2][r][:n_rows]),
                "active": np.asarray(rows[3][r][:n_rows]),
            }
    return out
