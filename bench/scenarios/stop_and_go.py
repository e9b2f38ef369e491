"""Ring road with a periodic braking pulse: ``n_lanes`` lanes closed into
a ring of ``min(road_len, max(n_slots, 8) * 30 / n_lanes)`` metres. A
vehicle with no leader follows its lane's rearmost vehicle across the
seam; every ``aux1`` seconds, for 5 s, vehicles in the band from 45 % to
55 % of the ring brake at ``aux0``. MOBIL only between 10 % and 90 % of
the ring, no exits, positions wrap; the gauge counts vehicles under
2 m/s."""

import jax
import jax.numpy as jnp

from reference import INF, Road, idm


def road(sim):
    n, length = sim["n_lanes"], sim["road_len"]
    ring = min(length, max(sim["n_slots"], 8) * 30.0 / n)
    return Road(n, n, ring, 0.0, 0.0, True)


def sample_params(key, sim):
    n = sim["n_lanes"]
    u = jax.random.uniform
    z = jnp.zeros(())
    k1, k2, k3, _, k5, k6 = jax.random.split(key, 6)
    v0 = u(k3, (), minval=26.0, maxval=33.0)
    return (u(k1, (n,), minval=0.25, maxval=0.70), z,
            u(k2, (), minval=0.0, maxval=1.0), v0, v0,
            u(k5, (), minval=2.0, maxval=5.0),
            u(k6, (), minval=20.0, maxval=45.0))


def context(v, sim, rd):
    """Each lane's rearmost vehicle: position, speed, length."""
    ls = jnp.arange(rd.n_lanes)
    keyed = jnp.where(v.active[None, :] & (v.lane[None, :] == ls[:, None]),
                      v.pos[None, :], INF)
    rear = jnp.argmin(keyed, axis=1)
    return jnp.min(keyed, axis=1), v.vel[rear], v.length[rear]


def limits(v, sim, rd, p, qlane, nb, ctx, a):
    has_lead = nb[2]
    rear_pos, rear_vel, rear_len = ctx
    q = jnp.clip(qlane, 0, rd.n_lanes - 1)
    a_wrap = idm(v.vel, v.vel - rear_vel[q],
                 rear_pos[q] + rd.length - v.pos - rear_len[q],
                 v.v0, v.T, v.a_max, v.b_comf, v.s0)
    a = jnp.where(~has_lead & (rear_pos[q] < INF * 0.5),
                  jnp.minimum(a, a_wrap), a)
    phase = jnp.mod(v.t.astype(jnp.float32) * sim["dt"],
                    jnp.maximum(p[6], 1.0))
    band = (v.pos >= 0.45 * rd.length) & (v.pos <= 0.55 * rd.length)
    return jnp.where((phase < 5.0) & band, jnp.minimum(a, -p[5]), a)


def mobil_eligible(v, sim, rd):
    return ((v.lane < rd.n_lanes) & (v.pos > 0.1 * rd.length)
            & (v.pos < 0.9 * rd.length))


def clamp(v, sim, rd, pos, vel):
    return jnp.where(v.active, jnp.mod(pos, rd.length), pos), vel


def exits(v, sim, rd):
    return jnp.zeros_like(v.active)


def gauge(v, sim, rd):
    return v.active & (v.vel < 2.0)
