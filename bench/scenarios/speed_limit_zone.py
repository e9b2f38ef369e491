"""Work zone: a speed limit ``aux0`` between ``merge_start`` and
``merge_end``. Inside, acceleration is held under
``a_max (1 - (v / limit)^4)``; before the zone, a vehicle above the limit brakes as if behind a
vehicle at the zone's start moving at the limit. The gauge counts the
vehicles inside the zone."""

import jax
import jax.numpy as jnp

from reference import Road, idm


def road(sim):
    n = sim["n_lanes"]
    return Road(n, n, sim["road_len"], sim["merge_start"], sim["merge_end"],
                False)


def sample_params(key, sim):
    n = sim["n_lanes"]
    z = jnp.zeros(())
    u = jax.random.uniform
    k1, k2, k3, _, k5 = jax.random.split(key, 5)
    v0 = u(k3, (), minval=26.0, maxval=33.0)
    return (u(k1, (n,), minval=0.15, maxval=0.55), z,
            u(k2, (), minval=0.0, maxval=1.0), v0, v0,
            u(k5, (), minval=10.0, maxval=18.0), z)


def limits(v, sim, rd, p, qlane, nb, ctx, a):
    limit = jnp.maximum(p[5], 0.1)
    zone = (v.pos >= rd.zone_start) & (v.pos <= rd.zone_end)
    a = jnp.where(zone, jnp.minimum(a, v.a_max * (1.0 - (v.vel / limit) ** 4)),
                  a)
    a_in = idm(v.vel, v.vel - limit, rd.zone_start - v.pos, v.v0, v.T,
               v.a_max, v.b_comf, v.s0)
    return jnp.where((v.pos < rd.zone_start) & (v.vel > limit),
                     jnp.minimum(a, a_in), a)


def gauge(v, sim, rd):
    return v.active & (v.pos >= rd.zone_start) & (v.pos <= rd.zone_end)
