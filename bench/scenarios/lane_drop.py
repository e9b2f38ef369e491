"""Lane drop: lane 0 ends at ``merge_end``. Its vehicles brake against the
end wall and move into lane 1 inside the taper when the gaps there are
accepted; MOBIL may not move a vehicle into lane 0 once past
``merge_start``; the gauge counts lane-0 vehicles stopped at the wall."""

import jax
import jax.numpy as jnp

from reference import Road, gaps_ok, wall, wall_clamp, wall_gauge


def road(sim):
    n = sim["n_lanes"]
    return Road(n, n, sim["road_len"], sim["merge_start"], sim["merge_end"],
                False)


def sample_params(key, sim):
    n = sim["n_lanes"]
    z = jnp.zeros(())
    u = jax.random.uniform
    k1, k2, k3, _ = jax.random.split(key, 4)
    v0 = u(k3, (), minval=26.0, maxval=33.0)
    return (u(k1, (n,), minval=0.25, maxval=0.65), z,
            u(k2, (), minval=0.0, maxval=1.0), v0, v0, z, z)


def limits(v, sim, rd, p, qlane, nb, ctx, a):
    return wall(v, rd.zone_end, qlane == 0, a)


def mobil_allowed(v, sim, rd, cand):
    return ~((cand == 0) & (v.lane != 0) & (v.pos >= rd.zone_start))


def forced(v, sim, rd, tables, lane):
    zone = (v.pos >= rd.zone_start) & (v.pos <= rd.zone_end)
    move = ((v.lane == 0) & v.active & zone
            & gaps_ok(v, sim, tables, jnp.full_like(v.lane, 1)))
    return jnp.where(move, 1, lane), jnp.sum(move.astype(jnp.int32))


def clamp(v, sim, rd, pos, vel):
    return wall_clamp(rd.zone_end, v.lane == 0, pos, vel)


def gauge(v, sim, rd):
    return wall_gauge(v, rd.zone_end, v.lane == 0)
