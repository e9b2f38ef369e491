"""Work-zone speed limit on a straight pipe — throughput under a slow zone.

Geometry::

      lane 2  ──────────────▶ ┊ limit aux0 m/s ┊ ──────────▶
      lane 1  ──────────────▶ ┊                ┊ ──────────▶
      lane 0  ──────────────▶ ┊   work zone    ┊ ──────────▶
                         zone_start        zone_end

A reduced-speed zone spanning all lanes on an otherwise plain highway
(``SimConfig.merge_start/merge_end`` are read as the zone extent). The
zone's limit is the per-instance knob ``aux0`` (sampled 10–18 m/s), so a
sweep covers the limit–throughput response surface.

Hook usage — this scenario is *pure* ``longitudinal_mods``:

- inside the zone, acceleration is capped by IDM's free-road term toward the
  limit speed, so vehicles track the limit instead of their desired ``v0``;
- approaching vehicles anticipate: upstream of ``zone_start`` they follow a
  virtual leader moving at the limit located at the zone entrance — smooth
  deceleration instead of a braking shock at the boundary.

Everything else is the base open-road behavior: MOBIL everywhere, spawn on
every lane, exit past ``road_len``. The gauge counts vehicles inside the
zone (occupancy, → ``zone_veh_steps``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.scenario import ScenarioParams, SimConfig
from repro.core.scenarios.base import (
    RoadGeometry,
    Scenario,
    speed_limit_mods,
)


class SpeedLimitZone(Scenario):
    name = "speed_limit_zone"
    metric_aliases = {
        "ramp_blocked_steps": "zone_veh_steps",
    }

    def geometry(self, cfg: SimConfig) -> RoadGeometry:
        return RoadGeometry(
            n_lanes=cfg.n_lanes,
            road_len=cfg.road_len,
            zone_start=cfg.merge_start,
            zone_end=cfg.merge_end,
        )

    def sample_params(self, key: jax.Array, cfg: SimConfig) -> ScenarioParams:
        k1, k2, k3, k4, k5 = jax.random.split(key, 5)
        z = jnp.zeros(())
        lambda_main = jax.random.uniform(
            k1, (cfg.n_lanes,), minval=0.15, maxval=0.55
        )
        p_cav = jax.random.uniform(k2, (), minval=0.0, maxval=1.0)
        v0_mean = jax.random.uniform(k3, (), minval=26.0, maxval=33.0)
        seed = jax.random.randint(k4, (), 0, 2**31 - 1).astype(jnp.uint32)
        limit = jax.random.uniform(k5, (), minval=10.0, maxval=18.0)  # aux0
        return ScenarioParams(
            lambda_main=lambda_main, lambda_ramp=z, p_cav=p_cav,
            v0_mean=v0_mean, v0_ramp=v0_mean, seed=seed, aux0=limit, aux1=z,
        )

    # ---------------- longitudinal: the zone ----------------

    def longitudinal_mods(self, st, cfg, geom, sp, query_lane, nb, a,
                          ctx=None):
        # inside the zone: free-road IDM toward the limit speed caps accel;
        # upstream anticipation: follow a virtual leader at the zone
        # entrance moving at the limit speed
        in_zone = (st.pos >= geom.zone_start) & (st.pos <= geom.zone_end)
        return speed_limit_mods(st, jnp.maximum(sp.aux0, 0.1), in_zone,
                                st.pos < geom.zone_start, geom.zone_start, a)

    # ---------------- boundary: zone occupancy gauge ----------------

    def boundary_gauge(self, st, cfg, geom):
        in_zone = (
            st.active & (st.pos >= geom.zone_start)
            & (st.pos <= geom.zone_end)
        )
        return jnp.sum(in_zone.astype(jnp.int32))
