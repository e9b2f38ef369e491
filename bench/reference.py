"""Plain reference of the traffic microsimulation the sweep runs.

Written from the model's equations, not from the program: it imports
nothing of ``repro``. Given a deployment's configuration (the JSON file
under ``bench/configs``) it rebuilds one instance from the sweep seed and
its instance id, steps it one vehicle step at a time, and records what the
sweep's recorder records:

- demand: per-lane Bernoulli arrivals of rate ``lambda * dt`` at the
  lane's entry position, admitted when the nearest vehicle in the lane is
  past ``spawn_gap`` from it (on a ring also when the seam has room), each
  arrival taking the lowest free slot in lane order, with human or CAV
  driver parameters (``driver_tables``), humans jittered by U(0.85, 1.15);
- car following: the Intelligent Driver Model against the nearest
  vehicle strictly ahead in the lane (an all-pairs search per lane); a
  gap is measured to the leader's rear, so it takes off the leader's
  length, and a follower's gap takes off the vehicle's own;
- lane changes: MOBIL (incentive with politeness, safety ``b_safe``,
  threshold ``mobil_athr``, cooldown), then the scenario's forced moves;
- collisions (overlap with the followed leader removes the follower),
  the scenario's exits, time to collision over closing pairs.

What differs between scenarios lives in one module per scenario,
``bench/scenarios/<registry name>.py``, found by file from ``SCENARIOS``
(``scenario(name)``); a new scenario is a new file there. A module gives:

- ``road(sim) -> Road`` and ``sample_params(key, sim)`` (required): the
  geometry and the instance's parameter draw, ``(lambda_main[n],
  lambda_ramp, p_cav, v0_mean, v0_ramp, aux0, aux1)``;
- ``spawn_lanes(sim, rd, p) -> (lanes, lam, base_v0, entry)``: the lanes
  that spawn, their rates, base desired speeds and entry positions
  (default: every main lane at ``lambda_main``, ``v0_mean``, position 0);
- ``context(v, sim, rd)``: anything computed once per neighbour snapshot
  for ``limits`` (default ``None``; the ring's rear scan);
- ``limits(v, sim, rd, p, qlane, nb, ctx, a) -> a``: extra acceleration
  limits before the clamp to ``[-b_max, a_max]`` (default none);
- ``mobil_eligible(v, sim, rd)``: who may make MOBIL changes, before
  activity and cooldown (default: vehicles on main lanes), and
  ``mobil_allowed(v, sim, rd, cand)``: its veto of targets (default none);
- ``forced(v, sim, rd, tables, lane) -> (lane, n)``: mandatory moves after
  MOBIL and their count (``merges_ok``; default none);
- ``clamp(v, sim, rd, pos, vel) -> (pos, vel)``: position rules after the
  move (default none), ``exits(v, sim, rd)``: the exit predicate (default:
  active and past ``rd.length``), ``gauge(v, sim, rd)``: the vehicles the
  congestion gauge counts this step (``blocked``; default none).

Three extension points that none of the four scenarios uses:

- ``FIELDS = {name: free-slot default}``: per-vehicle fields (an int
  default makes an int32 field, a bool a boolean one, a float one in
  ``dtype``), drawn at spawn by ``draw(key, sim, rd, p, lanes, cav) ->
  {name: value per spawn lane}`` from a key derived after every other
  draw, carried in ``Veh.extra`` and returned under ``veh``; ``EXACT``
  names those the program also holds (in its ``SimState``) and that the
  comparison counts as ``slot_mismatch`` where they differ;
- vehicle length: ``Veh.length``, ``sim["vehicle_len"]`` in every slot
  unless ``draw`` returns a ``length``;
- ``COUNTERS = {reference name: SimMetrics field}``: counters of its own,
  incremented by ``tally(v, sim, rd, p, exited, crashed) -> {name: n}``
  before exits and collisions remove vehicles, returned under
  ``counters`` and compared exactly (``check.counter_names``).

``dtype`` is the precision of every real-valued state, parameter and
accumulator: ``float32`` as the configuration states, ``bfloat16`` for the
control that a comparison has to fail.
"""

from __future__ import annotations

import functools
import importlib.util
import os
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

INF = 1e9
SCENARIOS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "scenarios")


class Road(NamedTuple):
    n_lanes: int        # main lanes
    n_total: int        # lane-table rows (main lanes + ramp)
    length: float
    zone_start: float
    zone_end: float
    ring: bool


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
    length: jax.Array
    extra: dict         # the scenario's FIELDS, name -> per-slot array


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
    extra: dict         # the scenario's COUNTERS, reference name -> count


# ---------------------------------------------------------------------------
# scenario modules
# ---------------------------------------------------------------------------

def _spawn_lanes(sim, rd: Road, p):
    lanes = jnp.arange(rd.n_lanes)
    base_v0 = jnp.full((rd.n_lanes,), 1.0) * p[3]
    return lanes, p[0], base_v0, jnp.zeros_like(p[0])


def _forced(v: Veh, sim, rd: Road, tables, lane):
    return lane, jnp.zeros((), jnp.int32)


DEFAULTS = {
    "spawn_lanes": _spawn_lanes,
    "context": lambda v, sim, rd: None,
    "limits": lambda v, sim, rd, p, qlane, nb, ctx, a: a,
    "mobil_eligible": lambda v, sim, rd: v.lane < rd.n_lanes,
    "mobil_allowed": lambda v, sim, rd, cand: True,
    "forced": _forced,
    "clamp": lambda v, sim, rd, pos, vel: (pos, vel),
    "exits": lambda v, sim, rd: v.active & (v.pos > rd.length),
    "gauge": lambda v, sim, rd: jnp.zeros_like(v.active),
    "draw": lambda key, sim, rd, p, lanes, cav: {},
    "tally": lambda v, sim, rd, p, exited, crashed: {},
}
REQUIRED = ("road", "sample_params")
HOOKS = REQUIRED + tuple(DEFAULTS)


class Scenario(NamedTuple):
    """One scenario module's hooks (``DEFAULTS`` where it gives none) and
    declarations."""
    road: Callable
    sample_params: Callable
    spawn_lanes: Callable
    context: Callable
    limits: Callable
    mobil_eligible: Callable
    mobil_allowed: Callable
    forced: Callable
    clamp: Callable
    exits: Callable
    gauge: Callable
    draw: Callable
    tally: Callable
    fields: dict        # per-vehicle field -> free-slot default
    exact: tuple        # fields the program holds too, compared exactly
    counters: dict      # reference name -> SimMetrics field


def known(directory: str | None = None) -> list[str]:
    """The scenario modules in ``directory`` (default ``SCENARIOS``)."""
    directory = directory or SCENARIOS
    return sorted(f[:-3] for f in os.listdir(directory)
                  if f.endswith(".py") and not f.startswith("_"))


def scenario(name: str) -> Scenario:
    """The module ``SCENARIOS/<name>.py``; an unknown name is a KeyError
    that lists the known ones."""
    return _load(SCENARIOS, name)


@functools.cache
def _load(directory: str, name: str) -> Scenario:
    path = os.path.join(directory, name + ".py")
    if not (name.isidentifier() and os.path.isfile(path)):
        raise KeyError(f"the reference has no scenario {name!r}; "
                       f"known: {known(directory)}")
    spec = importlib.util.spec_from_file_location(f"bench_scenario_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    missing = [h for h in REQUIRED if not hasattr(mod, h)]
    if missing:
        raise TypeError(f"scenario module {path} lacks {missing}")
    hooks = {h: getattr(mod, h, DEFAULTS.get(h)) for h in HOOKS}
    sc = Scenario(**hooks, fields=dict(getattr(mod, "FIELDS", {})),
                  exact=tuple(getattr(mod, "EXACT", ())),
                  counters=dict(getattr(mod, "COUNTERS", {})))
    bad = ((set(sc.fields) & set(Veh._fields))
           | (set(sc.exact) - set(sc.fields) - {"length"})
           | (set(sc.counters) & set(Counters._fields)))
    if bad:
        raise ValueError(f"scenario module {path}: {sorted(bad)} clash "
                         "with the core's state or are not declared")
    return sc


# ---------------------------------------------------------------------------
# instance draws
# ---------------------------------------------------------------------------

def _field(default, n: int, dtype):
    if isinstance(default, bool):
        return jnp.full((n,), default, bool)
    if isinstance(default, int):
        return jnp.full((n,), default, jnp.int32)
    return jnp.full((n,), default, dtype)


def instance(seed: int, i: int, cfg: dict, dtype=jnp.float32):
    """Scenario name, parameters, empty world, counters and horizon of
    instance ``i`` of the sweep with seed ``seed``."""
    sim, sweep = cfg["sim"], cfg["sweep"]
    roster = cfg["roster"]
    name = roster[i % len(roster)]
    sc = scenario(name)
    k = jax.random.fold_in(jax.random.key(seed), i)
    params = sc.sample_params(jax.random.fold_in(k, 1), sim)
    params = tuple(jnp.asarray(p, dtype) for p in params)
    n = sim["n_slots"]
    h = cfg["driver_tables"]["human"]
    zf = jnp.zeros((n,), dtype)
    veh = Veh(zf - INF, zf, jnp.zeros((n,), jnp.int32), jnp.zeros((n,), bool),
              jnp.zeros((n,), bool), zf + 30.0, zf + h["T"], zf + h["a_max"],
              zf + h["b_comf"], zf + h["s0"], zf + h["politeness"],
              jnp.zeros((n,), jnp.int32), jax.random.fold_in(k, 2),
              jnp.zeros((), jnp.int32), zf + sim["vehicle_len"],
              {f: _field(d, n, dtype) for f, d in sc.fields.items()})
    zi, z = jnp.zeros((), jnp.int32), jnp.zeros((), dtype)
    counters = Counters(zi, zi, z, z, zi, zi, zi, zi, jnp.asarray(INF, dtype),
                        zi, {c: zi for c in sc.counters})
    steps = sweep["steps_per_instance"]
    if sweep["vary_horizon"]:
        frac = jax.random.uniform(jax.random.fold_in(k, 3), (),
                                  minval=sweep["min_horizon_frac"],
                                  maxval=1.0)
        horizon = int((frac * steps).astype(jnp.int32))
    else:
        horizon = steps
    return name, params, veh, counters, horizon


# ---------------------------------------------------------------------------
# physics
# ---------------------------------------------------------------------------

def idm(v, dv, gap, v0, T, a_max, b_comf, s0):
    gap = jnp.maximum(gap, 0.1)
    s_star = s0 + jnp.maximum(
        0.0, v * T + v * dv / (2.0 * jnp.sqrt(a_max * b_comf)))
    free = (v / jnp.maximum(v0, 0.1)) ** 4
    return a_max * (1.0 - free - (s_star / gap) ** 2)


def neighbours(pos, lane, active, length, qlane):
    """Nearest vehicle strictly ahead / behind each vehicle in lane
    ``qlane[i]``; ties go to the lowest slot; an inactive vehicle has none.
    The lead gap ends at the leader's rear, the follower gap at the
    vehicle's own. Returns (lead, lead_gap, has_lead, foll, foll_gap,
    has_foll)."""
    d = pos[None, :] - pos[:, None]
    n = pos.shape[0]
    ok = ((lane[None, :] == qlane[:, None]) & active[None, :]
          & active[:, None] & ~jnp.eye(n, dtype=bool))
    ahead, behind = ok & (d > 0.0), ok & (d < 0.0)
    ld = jnp.where(ahead, d, INF)
    fd = jnp.where(behind, -d, INF)
    lead = jnp.argmin(ld, axis=1)
    return (lead, jnp.min(ld, axis=1) - length[lead],
            jnp.any(ahead, axis=1), jnp.argmin(fd, axis=1),
            jnp.min(fd, axis=1) - length, jnp.any(behind, axis=1))


def lane_tables(v: Veh, n_total):
    return jax.vmap(
        lambda l: neighbours(v.pos, v.lane, v.active, v.length,
                             jnp.full_like(v.lane, l))
    )(jnp.arange(n_total))


def ask(tables, qlane):
    cols = jnp.arange(qlane.shape[0])
    return tuple(t[qlane, cols] for t in tables)


def wall(v: Veh, wall_pos, on, a):
    """IDM braking against a standing wall at ``wall_pos`` for ``on``."""
    a_wall = idm(v.vel, v.vel, wall_pos - v.pos, v.v0, v.T, v.a_max,
                 v.b_comf, v.s0)
    return jnp.where(on, jnp.minimum(a, a_wall), a)


def wall_clamp(wall_pos, on, pos, vel):
    """No driving past the wall; speed zeroes there."""
    pos = jnp.where(on, jnp.minimum(pos, wall_pos), pos)
    vel = jnp.where(on & (pos >= wall_pos), 0.0, vel)
    return pos, vel


def wall_gauge(v: Veh, wall_pos, on):
    """Vehicles stopped within 10 m of the wall."""
    return v.active & on & (v.pos > wall_pos - 10.0) & (v.vel < 0.5)


def accel(v: Veh, sim, rd: Road, sc: Scenario, p, qlane, nb, ctx):
    """IDM against the lead in ``qlane``, the scenario's extra limits, and
    the clamp to [-b_max, a_max]."""
    lead, lgap, has_lead = nb[0], nb[1], nb[2]
    v_lead = jnp.where(has_lead, v.vel[lead], 0.0)
    gap = jnp.where(has_lead, lgap, INF)
    dv = jnp.where(has_lead, v.vel - v_lead, 0.0)
    a = idm(v.vel, dv, gap, v.v0, v.T, v.a_max, v.b_comf, v.s0)
    a = sc.limits(v, sim, rd, p, qlane, nb, ctx, a)
    return jnp.clip(a, -sim["b_max"], v.a_max)


def mobil(v: Veh, sim, rd, sc, p, a_now, own, tables, cand, ctx):
    nb = ask(tables, cand)
    _, lg, hl, fi, fg, hf = nb
    a_new = accel(v, sim, rd, sc, p, cand, nb, ctx)
    a_j0 = jnp.where(hf, a_now[fi], 0.0)
    a_j1 = jnp.where(hf, idm(v.vel[fi], v.vel[fi] - v.vel,
                             jnp.where(hf, fg, INF), v.v0[fi], v.T[fi],
                             v.a_max[fi], v.b_comf[fi], v.s0[fi]), 0.0)
    lead, _, has_lead, ki, _, hk = own
    lead_pos = jnp.where(has_lead, v.pos[lead], INF)
    lead_vel = jnp.where(has_lead, v.vel[lead], 0.0)
    a_k0 = jnp.where(hk, a_now[ki], 0.0)
    a_k1 = jnp.where(hk, idm(v.vel[ki], v.vel[ki] - lead_vel,
                             lead_pos - v.pos[ki] - v.length[lead],
                             v.v0[ki], v.T[ki], v.a_max[ki], v.b_comf[ki],
                             v.s0[ki]), 0.0)
    gain = (a_new - a_now) + v.polite * ((a_j1 - a_j0) + (a_k1 - a_k0))
    safe = ((a_j1 >= -sim["b_safe"]) & (jnp.where(hf, fg, INF) > 0.0)
            & (jnp.where(hl, lg, INF) > 0.0))
    return gain, safe


def gaps_ok(v: Veh, sim, tables, target):
    """Gap acceptance for a mandatory move into ``target`` (CAVs accept
    0.7x gaps)."""
    _, lg, hl, _, fg, hf = ask(tables, target)
    scale = jnp.where(v.is_cav, 0.7, 1.0)
    return ((jnp.where(hl, lg, INF) > scale * sim["merge_gap_front"])
            & (jnp.where(hf, fg, INF) > scale * sim["merge_gap_rear"]))


def step(v: Veh, m: Counters, p, sim: dict, sc: Scenario, drivers: dict):
    rd = sc.road(sim)
    key, k_spawn = jax.random.split(v.key)
    v = v._replace(key=key)

    # lane changes on the pre-move snapshot
    tables = lane_tables(v, rd.n_total)
    ctx = sc.context(v, sim, rd)
    own = ask(tables, v.lane)
    a_now = accel(v, sim, rd, sc, p, v.lane, own, ctx)
    may = sc.mobil_eligible(v, sim, rd) & v.active & (v.cooldown == 0)
    left = jnp.minimum(v.lane + 1, rd.n_lanes - 1)
    right = jnp.maximum(v.lane - 1, 0)
    g_l, s_l = mobil(v, sim, rd, sc, p, a_now, own, tables, left, ctx)
    g_r, s_r = mobil(v, sim, rd, sc, p, a_now, own, tables, right, ctx)
    ok_l = (s_l & (g_l > sim["mobil_athr"]) & (left != v.lane) & may
            & sc.mobil_allowed(v, sim, rd, left))
    ok_r = (s_r & (g_r > sim["mobil_athr"]) & (right != v.lane) & may
            & sc.mobil_allowed(v, sim, rd, right))
    go_l = ok_l & (~ok_r | (g_l >= g_r))
    go_r = ok_r & ~go_l
    lane = jnp.where(go_l, left, jnp.where(go_r, right, v.lane))
    changed = go_l | go_r
    cooldown = jnp.where(changed, sim["lane_change_cooldown"],
                         jnp.maximum(v.cooldown - 1, 0))
    n_lc = jnp.sum(changed.astype(jnp.int32))
    lane, n_forced = sc.forced(v, sim, rd, tables, lane)
    v = v._replace(lane=lane, cooldown=cooldown)

    # move on the post-change snapshot
    nb = neighbours(v.pos, v.lane, v.active, v.length, v.lane)
    ctx = sc.context(v, sim, rd)
    a = jnp.where(v.active, accel(v, sim, rd, sc, p, v.lane, nb, ctx), 0.0)
    vel = jnp.maximum(v.vel + a * sim["dt"], 0.0)
    pos = v.pos + vel * sim["dt"]
    pos, vel = sc.clamp(v, sim, rd, pos, vel)
    v = v._replace(pos=pos, vel=vel)

    # collisions with the followed leader, exits, time to collision
    lead, has_lead = nb[0], nb[2]
    d = v.pos[lead] - v.pos
    if rd.ring:
        d = jnp.mod(d + 0.5 * rd.length, rd.length) - 0.5 * rd.length
    lgap = jnp.where(has_lead, d - v.length[lead], INF - v.length[lead])
    crashed = v.active & has_lead & (lgap < 0.0)
    exited = sc.exits(v, sim, rd)
    counts = sc.tally(v, sim, rd, p, exited, crashed)
    active = v.active & ~exited & ~crashed
    v = v._replace(active=active, pos=jnp.where(active, v.pos, -INF))
    dv = jnp.where(has_lead, v.vel - v.vel[lead], 0.0)
    ttc = jnp.where(v.active & has_lead & (dv > 0.1),
                    jnp.maximum(lgap, 0.0) / dv, INF)
    blocked = sc.gauge(v, sim, rd)

    v, n_spawn = spawn(v, p, sim, rd, sc, drivers, k_spawn)
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
        {c: x + jnp.sum(jnp.asarray(counts[c]).astype(jnp.int32))
         for c, x in m.extra.items()},
    )
    return v, m


def spawn(v: Veh, p, sim, rd: Road, sc: Scenario, drivers, key):
    n = v.pos.shape[0]
    lanes, lam, base_v0, entry = sc.spawn_lanes(sim, rd, p)
    p_cav = p[2]
    n_l = lanes.shape[0]
    ku, kj = jax.random.split(key)
    u = jax.random.uniform(ku, (3, n_l))
    arrive = u[0] < lam * sim["dt"]
    in_lane = v.active[None, :] & (v.lane[None, :] == lanes[:, None])
    nearest = jnp.min(jnp.where(in_lane, v.pos[None, :], INF), axis=1)
    room = nearest - entry
    clear = room > sim["spawn_gap"]
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
    init_v = jnp.minimum(new_v0, room / jnp.maximum(T, 0.5))
    # split(key) above gives fold_in(key, 0) and fold_in(key, 1): the
    # scenario's own draws take the next key, so they move none of those
    drawn = sc.draw(jax.random.fold_in(key, 2), sim, rd, p, lanes, cav)

    def put(arr, val):
        return arr.at[slot].set(val.astype(arr.dtype), mode="drop")

    v = v._replace(
        pos=put(v.pos, entry),
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
        length=(put(v.length, jnp.asarray(drawn["length"]))
                if "length" in drawn else v.length),
        extra={f: put(x, jnp.asarray(drawn[f])) if f in drawn else x
               for f, x in v.extra.items()},
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


@functools.partial(jax.jit, static_argnames=(
    "sim", "where", "drivers", "n_steps", "every", "k_slots"))
def _block(v, m, p, target, *, sim, where, drivers, n_steps, every,
           k_slots):
    """``n_steps`` steps of a batch of instances of the scenario module
    ``where`` (directory, name), each frozen once it reaches its own
    ``target``; one recorded row per ``every`` steps."""
    sim, drivers = dict(sim), {k: dict(d) for k, d in drivers}
    sc = _load(*where)

    def one(v, m, p, target):
        def body(carry, _):
            v, m = carry
            v2, m2 = step(v, m, p, sim, sc, drivers)
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
    for j, (name, *_rest) in enumerate(made):
        by_scenario.setdefault(name, []).append(j)
    for name, js in by_scenario.items():
        rows_of = js + [js[0]] * (len(made) - len(js))     # padded batch
        stack = lambda xs: jax.tree.map(lambda *a: jnp.stack(a), *xs)  # noqa: E731
        p = stack([made[j][1] for j in rows_of])
        v = stack([made[j][2] for j in rows_of])
        m = stack([made[j][3] for j in rows_of])
        ends = [min(int(targets[j]), made[j][4]) for j in rows_of]
        target = jnp.asarray(ends)
        run = functools.partial(
            _block, sim=_freeze(sim), where=(SCENARIOS, name), drivers=drivers,
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
                "scenario": name,
                "horizon": made[j][4],
                "t": ends[r],
                "veh": {f: np.asarray(x[r]) for f, x in
                        [*zip(Veh._fields, v), *v.extra.items()]
                        if f not in ("key", "extra")},
                "counters": {f: np.asarray(x[r]) for f, x in
                             [*zip(Counters._fields, m), *m.extra.items()]
                             if f != "extra"},
                "series": np.asarray(rows[0][r][:n_rows]),
                "lane": np.asarray(rows[1][r][:n_rows]),
                "speed": np.asarray(rows[2][r][:n_rows]),
                "active": np.asarray(rows[3][r][:n_rows]),
            }
    return out
