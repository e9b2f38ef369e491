"""Freeway weave, the NGSIM US-101 layout: ``n_lanes`` mainline lanes
and an auxiliary lane, lane ``n_lanes``, from the on-ramp (entering at
position 0) to the off-ramp gore at ``merge_end``; the weave zone runs
from ``merge_start`` to the gore.

Written from MOBIL's description (Kesting, Treiber, Helbing 2007, which
models a mandatory change as the end of the lane the vehicle must leave),
with these choices:

- routes: each arrival draws ``route`` (1 exit-bound, 0 through) from the
  key after every other draw: exit-bound with probability ``aux0`` on a
  mainline lane, through with probability ``aux1`` in the auxiliary lane;
- pacing: inside the zone an exit-bound vehicle in mainline lane ``q``
  has its acceleration held under ``a_max (1 - (v / cap)^4)``, ``cap =
  V_MIN + V_STEP max(r - q, 0)``, ``r = n_lanes (merge_end - pos) /
  (merge_end - merge_start)``: a lane nearer the auxiliary lane has the
  higher cap, so MOBIL's incentive moves the vehicle right, one lane per
  cooldown, under MOBIL's safety check. A cap and not a standing wall, so
  a vehicle that finds no gap slows its lane and never stops it;
- veto: inside the zone MOBIL may not move an exit-bound vehicle away from
  the auxiliary lane;
- forced moves inside the zone, under the merge's gap acceptance: an
  exit-bound vehicle in lane 0 whose cooldown is out moves into the
  auxiliary lane; a through vehicle in the auxiliary lane moves into lane
  0; both count in ``merges_ok``. A forced move leaves the cooldown as
  MOBIL set it;
- exits: the auxiliary lane past the gore (the off-ramp), the mainline
  past ``road_len``; ``exits_missed`` counts the exits that are wrong for
  the route (exit-bound on the mainline, through by the off-ramp);
- discharge: past the gore a mainline vehicle is held under
  ``a_max (1 - (v / discharge_speed)^4)``, and before the gore one faster
  than ``discharge_speed`` brakes as if behind a vehicle at the gore
  moving at it;
- the gauge counts exit-bound vehicles on the mainline between the last
  lane-change length before the gore, ``merge_end - (merge_end -
  merge_start) / n_lanes``, and the gore.
"""

import jax
import jax.numpy as jnp

from reference import Road, gaps_ok, idm

EXIT = 1
V_MIN = 3.0
V_STEP = 6.0

FIELDS = {"route": 0}
EXACT = ("route",)
COUNTERS = {"exits_missed": "exits_missed"}


def road(sim):
    n = sim["n_lanes"]
    return Road(n, n + 1, sim["road_len"], sim["merge_start"],
                sim["merge_end"], False)


def sample_params(key, sim):
    n = sim["n_lanes"]
    u = jax.random.uniform
    k1, k2, k3, k4, _, k6, k7 = jax.random.split(key, 7)
    v0 = u(k4, (), minval=26.0, maxval=33.0)
    return (u(k1, (n,), minval=0.45, maxval=0.60),
            u(k2, (), minval=0.10, maxval=0.25),
            u(k3, (), minval=0.0, maxval=0.2), v0, v0,
            u(k6, (), minval=0.05, maxval=0.20),
            u(k7, (), minval=0.6, maxval=0.9))


def spawn_lanes(sim, rd, p):
    lanes = jnp.arange(rd.n_lanes + 1)
    lam = jnp.concatenate([p[0], p[1][None]])
    base_v0 = jnp.where(lanes == rd.n_lanes, p[4], p[3])
    return lanes, lam, base_v0, jnp.zeros_like(lam)


def draw(key, sim, rd, p, lanes, cav):
    share = jnp.where(lanes == rd.n_lanes, 1.0 - p[6], p[5])
    return {"route": (jax.random.uniform(key, lanes.shape) < share)
            .astype(jnp.int32)}


def _zone(v, rd):
    return (v.pos >= rd.zone_start) & (v.pos <= rd.zone_end)


def limits(v, sim, rd, p, qlane, nb, ctx, a):
    main = qlane < rd.n_lanes
    limit = sim["discharge_speed"]
    a = jnp.where(main & (v.pos > rd.zone_end),
                  jnp.minimum(a, v.a_max * (1.0 - (v.vel / limit) ** 4)), a)
    a_in = idm(v.vel, v.vel - limit, rd.zone_end - v.pos, v.v0, v.T,
               v.a_max, v.b_comf, v.s0)
    a = jnp.where(main & (v.pos <= rd.zone_end) & (v.vel > limit),
                  jnp.minimum(a, a_in), a)
    r = (rd.zone_end - v.pos) * (rd.n_lanes / (rd.zone_end - rd.zone_start))
    cap = V_MIN + V_STEP * jnp.maximum(r - qlane.astype(v.pos.dtype), 0.0)
    on = main & (v.extra["route"] == EXIT) & _zone(v, rd)
    return jnp.where(on, jnp.minimum(a, v.a_max * (1.0 - (v.vel / cap) ** 4)),
                     a)


def mobil_allowed(v, sim, rd, cand):
    return ~((cand > v.lane) & (v.extra["route"] == EXIT) & _zone(v, rd))


def forced(v, sim, rd, tables, lane):
    aux = rd.n_lanes
    zone = v.active & _zone(v, rd)
    exiting = v.extra["route"] == EXIT
    out = (zone & exiting & (v.lane == 0) & (v.cooldown == 0)
           & gaps_ok(v, sim, tables, jnp.full_like(v.lane, aux)))
    into = (zone & ~exiting & (v.lane == aux)
            & gaps_ok(v, sim, tables, jnp.zeros_like(v.lane)))
    return (jnp.where(out, aux, jnp.where(into, 0, lane)),
            jnp.sum((out | into).astype(jnp.int32)))


def exits(v, sim, rd):
    ramp = (v.lane == rd.n_lanes) & (v.pos > rd.zone_end)
    return v.active & (ramp | (v.pos > rd.length))


def tally(v, sim, rd, p, exited, crashed):
    wrong = (v.extra["route"] == EXIT) != (v.lane == rd.n_lanes)
    return {"exits_missed": exited & wrong}


def gauge(v, sim, rd):
    last = rd.zone_end - (rd.zone_end - rd.zone_start) / rd.n_lanes
    return (v.active & (v.extra["route"] == EXIT) & (v.lane < rd.n_lanes)
            & (v.pos >= last) & (v.pos <= rd.zone_end))
