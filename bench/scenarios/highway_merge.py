"""Highway on-ramp merge: main lanes ``0 .. n_lanes - 1`` and a ramp,
lane ``n_lanes``, that ends at ``merge_end``. Ramp vehicles spawn at
``lambda_ramp`` and ``0.7 v0``, take no MOBIL changes, brake against the
ramp's end wall, and merge into lane 0 inside the merge zone when the
gaps there are accepted; the gauge counts ramp vehicles stopped at the
wall."""

import jax
import jax.numpy as jnp

from reference import Road, gaps_ok, wall, wall_clamp, wall_gauge


def road(sim):
    n = sim["n_lanes"]
    return Road(n, n + 1, sim["road_len"], sim["merge_start"],
                sim["merge_end"], False)


def sample_params(key, sim):
    n = sim["n_lanes"]
    z = jnp.zeros(())
    u = jax.random.uniform
    k1, k2, k3, k4, _ = jax.random.split(key, 5)
    v0 = u(k4, (), minval=26.0, maxval=33.0)
    return (u(k1, (n,), minval=0.15, maxval=0.55),
            u(k2, (), minval=0.05, maxval=0.30),
            u(k3, (), minval=0.0, maxval=1.0), v0, v0 * 0.7, z, z)


def spawn_lanes(sim, rd, p):
    lanes = jnp.arange(rd.n_lanes + 1)
    lam = jnp.concatenate([p[0], p[1][None]])
    base_v0 = jnp.where(lanes == rd.n_lanes, p[4], p[3])
    return lanes, lam, base_v0, jnp.zeros_like(lam)


def limits(v, sim, rd, p, qlane, nb, ctx, a):
    return wall(v, rd.zone_end, qlane == rd.n_lanes, a)


def forced(v, sim, rd, tables, lane):
    zone = (v.pos >= rd.zone_start) & (v.pos <= rd.zone_end)
    move = ((v.lane == rd.n_lanes) & v.active & zone
            & gaps_ok(v, sim, tables, jnp.zeros_like(v.lane)))
    return jnp.where(move, 0, lane), jnp.sum(move.astype(jnp.int32))


def clamp(v, sim, rd, pos, vel):
    return wall_clamp(rd.zone_end, v.lane == rd.n_lanes, pos, vel)


def gauge(v, sim, rd):
    return wall_gauge(v, rd.zone_end, v.lane == rd.n_lanes)
