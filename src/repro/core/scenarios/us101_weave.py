"""A freeway weaving section: the NGSIM US-101 (Hollywood Freeway) layout.

Geometry (FHWA fact sheet FHWA-HRT-07-030: about 640 m, five mainline
lanes, an auxiliary lane from the Ventura Blvd on-ramp to the Cahuenga
Blvd off-ramp)::

      lane 4  ──────────────────────────────────────────────────▶
        ...                                 ┊ discharge limit
      lane 0  ──────────────────────────────┊───────────────────▶
      aux(5)  ══on-ramp══╤═══ weave zone ═══╡ gore ──▶ off-ramp
              0     zone_start          zone_end          road_len

Every vehicle draws a route at spawn (``SimState.route``): on the
mainline it is exit-bound with probability ``aux0`` (the exit share), in
the auxiliary lane it is through with probability ``aux1`` (the on-ramp's
through share); ``SimConfig.merge_start``/``merge_end`` are read as the
weave zone's start and the off-ramp gore. The rules follow MOBIL (Kesting,
Treiber, Helbing 2007), which models a mandatory change as a lane that
ends for the vehicle that must leave it:

- pacing (``longitudinal_mods``): inside the weave zone an exit-bound
  vehicle in mainline lane ``q`` is held under a cap like
  ``speed_limit_zone``'s, toward ``V_MIN + V_STEP * max(r - q, 0)`` m/s,
  where ``r = n_lanes * (zone_end - pos) / (zone_end - zone_start)`` is
  the road left in lane-change lengths. A lane nearer the auxiliary lane
  has the higher cap, so MOBIL's incentive carries the vehicle rightward,
  one lane per cooldown, with MOBIL's own safety check. A cap and not a
  standing wall: a vehicle that finds no gap never stops its lane;
- vetoes (``mobil_candidate_ok``): no MOBIL move away from the auxiliary
  lane for an exit-bound vehicle inside the zone;
- forced moves (``lateral_rules``), with the ramp merge's gap acceptance,
  inside the zone: exit-bound vehicles from lane 0 into the auxiliary lane
  once their cooldown is out, through vehicles from the auxiliary lane
  into lane 0; both count in ``merges_ok`` (``weave_moves``);
- exits (``boundary_exit``): the auxiliary lane leaves by the off-ramp at
  the gore, the mainline at ``road_len``. A route failure leaves all the
  same and counts in ``exits_missed`` (``boundary_missed``): an exit-bound
  vehicle that reaches the gore outside the auxiliary lane drives on, a
  through vehicle still in it takes the off-ramp;
- discharge: past the gore the mainline is held under
  ``SimConfig.discharge_speed`` (``speed_limit_mods``, the work zone's cap
  with its anticipation before the gore), a downstream bottleneck that
  holds the queue a 640 m segment does not build by itself;
- the gauge counts exit-bound vehicles still on the mainline within the
  zone's last lane-change length before the gore (``late_exit_steps``).

The route rules run under the ``weave`` device scope.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.scenario import ScenarioParams, SimConfig
from repro.core.scenarios.base import (
    RoadGeometry,
    Scenario,
    gap_acceptance,
    speed_limit_mods,
)
from repro.core.trace import WEAVE

EXIT = 1               # SimState.route of an exit-bound vehicle
V_MIN = 3.0            # m/s, the cap at a lane's last change point
V_STEP = 6.0           # m/s more per lane-change length of road left


class US101Weave(Scenario):
    name = "us101_weave"
    metric_aliases = {
        "merges_ok": "weave_moves",
        "ramp_blocked_steps": "late_exit_steps",
    }

    def geometry(self, cfg: SimConfig) -> RoadGeometry:
        return RoadGeometry(
            n_lanes=cfg.n_lanes,
            road_len=cfg.road_len,
            special_lane="ramp",
            zone_start=cfg.merge_start,
            zone_end=cfg.merge_end,
        )

    def sample_params(self, key: jax.Array, cfg: SimConfig) -> ScenarioParams:
        k1, k2, k3, k4, k5, k6, k7 = jax.random.split(key, 7)
        lambda_main = jax.random.uniform(
            k1, (cfg.n_lanes,), minval=0.45, maxval=0.60
        )
        lambda_ramp = jax.random.uniform(k2, (), minval=0.10, maxval=0.25)
        p_cav = jax.random.uniform(k3, (), minval=0.0, maxval=0.2)
        v0_mean = jax.random.uniform(k4, (), minval=26.0, maxval=33.0)
        seed = jax.random.randint(k5, (), 0, 2**31 - 1).astype(jnp.uint32)
        exit_share = jax.random.uniform(k6, (), minval=0.05, maxval=0.20)
        through_share = jax.random.uniform(k7, (), minval=0.6, maxval=0.9)
        return ScenarioParams(
            lambda_main=lambda_main, lambda_ramp=lambda_ramp, p_cav=p_cav,
            v0_mean=v0_mean, v0_ramp=v0_mean, seed=seed,
            aux0=exit_share, aux1=through_share,
        )

    # ---------------- longitudinal: discharge and route pacing ------------

    def longitudinal_mods(self, st, cfg, geom, sp, query_lane, nb, a,
                          ctx=None):
        main = query_lane < geom.n_lanes
        if cfg.discharge_speed > 0.0:
            past = st.pos > geom.zone_end
            a = speed_limit_mods(st, cfg.discharge_speed, main & past,
                                 main & ~past, geom.zone_end, a)
        with jax.named_scope(WEAVE):
            per_m = geom.n_lanes / (geom.zone_end - geom.zone_start)
            behind = ((geom.zone_end - st.pos) * per_m
                      - query_lane.astype(st.pos.dtype))
            v_cap = V_MIN + V_STEP * jnp.maximum(behind, 0.0)
            a_cap = st.a_max * (1.0 - (st.vel / v_cap) ** 4)
            capped = (main & (st.route == EXIT) & self._in_zone(st, geom))
            return jnp.where(capped, jnp.minimum(a, a_cap), a)

    # ---------------- lateral: vetoes and forced moves --------------------

    @staticmethod
    def _in_zone(st, geom):
        return (st.pos >= geom.zone_start) & (st.pos <= geom.zone_end)

    def mobil_candidate_ok(self, st, cfg, geom, cand_lane):
        with jax.named_scope(WEAVE):
            away = ((cand_lane > st.lane) & (st.route == EXIT)
                    & self._in_zone(st, geom))
            return ~away

    def lateral_rules(self, st, cfg, geom, sp, tabs, mobil_lane):
        with jax.named_scope(WEAVE):
            aux = geom.n_lanes
            zone = st.active & self._in_zone(st, geom)
            exiting = st.route == EXIT
            to_aux = (zone & exiting & (st.lane == 0) & (st.cooldown == 0)
                      & gap_acceptance(st, cfg, tabs,
                                       jnp.full_like(st.lane, aux)))
            to_main = (zone & ~exiting & (st.lane == aux)
                       & gap_acceptance(st, cfg, tabs,
                                        jnp.zeros_like(st.lane)))
            lane = jnp.where(to_aux, aux, jnp.where(to_main, 0, mobil_lane))
            return lane, jnp.sum((to_aux | to_main).astype(jnp.int32))

    # ---------------- boundary: demand, routes, two exits, gauge ----------

    def boundary_spawn(self, cfg, geom, sp):
        lanes = jnp.arange(geom.n_lanes + 1)
        lam = jnp.concatenate([sp.lambda_main, sp.lambda_ramp[None]])
        base_v0 = jnp.where(lanes == geom.n_lanes, sp.v0_ramp, sp.v0_mean)
        return lam, base_v0, lanes

    def boundary_route(self, cfg, geom, sp, key, lanes):
        with jax.named_scope(WEAVE):
            exit_share = jnp.where(lanes == geom.n_lanes, 1.0 - sp.aux1,
                                   sp.aux0)
            u = jax.random.uniform(key, lanes.shape)
            return (u < exit_share).astype(jnp.int32)

    def boundary_exit(self, st, cfg, geom):
        off_ramp = (st.lane == geom.n_lanes) & (st.pos > geom.zone_end)
        return st.active & (off_ramp | (st.pos > geom.road_len))

    def boundary_missed(self, st, cfg, geom, exited):
        with jax.named_scope(WEAVE):
            wrong = (st.route == EXIT) != (st.lane == geom.n_lanes)
            return jnp.sum((exited & wrong).astype(jnp.int32))

    def boundary_gauge(self, st, cfg, geom):
        last = geom.zone_end - (geom.zone_end - geom.zone_start) / geom.n_lanes
        late = (st.active & (st.route == EXIT) & (st.lane < geom.n_lanes)
                & (st.pos >= last) & (st.pos <= geom.zone_end))
        return jnp.sum(late.astype(jnp.int32))
