"""The Scenario API: geometry + parameter sampling + three jit hook groups.

A *scenario* is everything about a simulation workload that is not the core
car-following physics: the road geometry, how an instance's randomized
parameters are drawn, and the scenario-specific rules the otherwise
scenario-agnostic ``sim_step`` (``repro.core.simulator``) applies each step.
GPU-batched simulators win by exactly this separation — one vectorized
physics core, pluggable task definitions — and it is what lets a single
compiled SPMD program sweep a *mix* of workloads (``SweepConfig.scenario_mix``
dispatches per-instance via ``lax.switch`` over registered step hooks).

A scenario implements three hook groups, all jit-compatible (pure functions
of traced arrays; the scenario object itself is static under jit because
``SimConfig`` is a static argument):

``longitudinal_mods(st, cfg, geom, sp, query_lane, nb, a, ctx) -> a``
    Extra acceleration constraints layered onto the base IDM accel *before*
    the ``[-b_max, a_max]`` clamp: the merge ramp's end-wall, a work-zone
    speed limit, a ring road's wrap-around leader, a periodic perturbation.
    ``ctx`` is the scenario's optional ``snapshot_ctx`` result, computed
    once per neighborhood snapshot and shared by all accel queries on it.

``lateral_rules``  (two methods)
    ``mobil_eligible(st, cfg, geom) -> bool[N]`` — which vehicles may make
    discretionary MOBIL lane changes (e.g. ramp vehicles may not), and
    ``lateral_rules(st, cfg, geom, sp, tabs, mobil_lane) -> (lane, n_moves)``
    — scenario-specific *mandatory* moves applied after MOBIL (gap-acceptance
    ramp merge, forced lane-drop exit, vetoes of illegal MOBIL targets).

``boundary``  (six methods)
    ``boundary_spawn(cfg, geom, sp) -> (lam, base_v0, lane_ids)`` — the
    demand process: which lanes spawn, at what rate, at what desired speed;
    ``boundary_route(cfg, geom, sp, key, lane_ids)`` — each arrival's
    ``SimState.route`` (0 through, 1 exit), or ``None`` for a scenario
    without routes (nothing is drawn and every route stays 0);
    ``boundary_clamp(st, cfg, geom, pos, vel)`` — post-integration position
    rules (ramp hard end, ring wrap); ``boundary_exit(st, cfg, geom)`` —
    the exit predicate; ``boundary_missed(st, cfg, geom, exited)`` — how
    many of the exits leave by the wrong way for their route
    (``SimMetrics.exits_missed``); ``boundary_gauge(st, cfg, geom)`` — the
    scenario's per-step congestion gauge (reported as
    ``SimMetrics.ramp_blocked_steps`` and renamed in records via
    ``metric_aliases``).

``SimMetrics`` is structurally identical across scenarios (a ``lax.switch``
requirement); ``metric_aliases`` maps the generic field names onto what they
mean for this scenario (e.g. ``merges_ok -> forced_merges`` for lane_drop).
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from repro.core.scenario import ScenarioParams, SimConfig

INF = 1e9


@dataclass(frozen=True)
class RoadGeometry:
    """Static road description a scenario derives from ``SimConfig``.

    Hashable (all-static) so it can parameterize jit-compiled steps.
    """

    n_lanes: int               # main lanes, indices [0, n_lanes)
    road_len: float
    special_lane: str = "none"  # "none" | "ramp" (extra lane n_lanes) |
    #                             "drop" (main lane 0 terminates at zone_end)
    zone_start: float = 0.0    # scenario zone extent (merge zone, bottleneck
    zone_end: float = 0.0      # taper, work zone, perturbation band anchor)
    ring: bool = False         # closed road: positions wrap mod road_len

    @property
    def n_lanes_total(self) -> int:
        """Lane-table size: main lanes plus the ramp lane if present."""
        return self.n_lanes + (1 if self.special_lane == "ramp" else 0)


def gap_acceptance(st, cfg: SimConfig, tabs, target_lane):
    """Per-vehicle mask: are the lead AND follower gaps in ``target_lane``
    acceptable for a mandatory merge? CAVs accept 0.7× gaps (cooperative
    merging). Shared by every scenario with a forced-merge lateral rule."""
    _, lg, hl, _, fg, hf = tabs.query(target_lane)
    front_need = jnp.where(st.is_cav, 0.7, 1.0) * cfg.merge_gap_front
    rear_need = jnp.where(st.is_cav, 0.7, 1.0) * cfg.merge_gap_rear
    return (
        (jnp.where(hl, lg, INF) > front_need)
        & (jnp.where(hf, fg, INF) > rear_need)
    )


def idm_accel(v, dv, gap, v0, T, a_max, b_comf, s0):
    """IDM acceleration. ``dv`` is the closing speed (v_self - v_lead)."""
    gap = jnp.maximum(gap, 0.1)
    s_star = s0 + jnp.maximum(
        0.0, v * T + v * dv / (2.0 * jnp.sqrt(a_max * b_comf))
    )
    free = (v / jnp.maximum(v0, 0.1)) ** 4
    return a_max * (1.0 - free - (s_star / gap) ** 2)


# Virtual dead-end wall: the shared physics of a lane that ends (the merge
# ramp, a lane-drop taper). A standing obstacle at ``wall_pos`` for the
# ``on_wall_lane`` vehicles — IDM braking on approach, a hard position
# clamp, and a "stuck at the wall" congestion gauge.

def end_wall_mods(st, wall_pos, on_wall_lane, a):
    """Brake ``on_wall_lane`` vehicles against a standing wall at
    ``wall_pos`` (layered onto the base accel via min)."""
    wall_gap = wall_pos - st.pos
    a_wall = idm_accel(
        st.vel, st.vel, wall_gap, st.v0, st.T, st.a_max, st.b_comf, st.s0
    )
    return jnp.where(on_wall_lane, jnp.minimum(a, a_wall), a)


def end_wall_clamp(wall_pos, on_wall_lane, pos, vel):
    """Hard dead end: cannot drive past the wall; speed zeroes there."""
    pos = jnp.where(on_wall_lane, jnp.minimum(pos, wall_pos), pos)
    vel = jnp.where(on_wall_lane & (pos >= wall_pos), 0.0, vel)
    return pos, vel


def end_wall_gauge(st, wall_pos, on_wall_lane):
    """Vehicle-steps stopped within 10 m of the wall (starvation gauge)."""
    blocked = (
        st.active & on_wall_lane
        & (st.pos > wall_pos - 10.0) & (st.vel < 0.5)
    )
    return jnp.sum(blocked.astype(jnp.int32))


def speed_limit_mods(st, limit, held, approaching, entry, a):
    """A speed limit: ``held`` vehicles' accel is capped by IDM's free-road
    term toward ``limit``; ``approaching`` ones faster than it brake as if
    behind a vehicle at ``entry`` moving at ``limit`` (layered via min)."""
    a_limit = st.a_max * (1.0 - (st.vel / limit) ** 4)
    a = jnp.where(held, jnp.minimum(a, a_limit), a)
    a_approach = idm_accel(
        st.vel, st.vel - limit, entry - st.pos,
        st.v0, st.T, st.a_max, st.b_comf, st.s0,
    )
    return jnp.where(approaching & (st.vel > limit),
                     jnp.minimum(a, a_approach), a)


class Scenario:
    """Base scenario: a plain multi-lane pipe with default everything.

    Subclasses override the hooks they need; the defaults are a straight
    open road — spawn on every main lane at ``lambda_main``, exit past
    ``road_len``, MOBIL everywhere, no extra accel constraints.
    """

    #: registry name (subclasses must set)
    name: str = "base"
    #: generic-metric-field → scenario-meaning renames for records/summaries
    metric_aliases: dict[str, str] = {}

    # ---------------- geometry + parameters ----------------

    def geometry(self, cfg: SimConfig) -> RoadGeometry:
        return RoadGeometry(n_lanes=cfg.n_lanes, road_len=cfg.road_len)

    def sample_params(self, key: jax.Array, cfg: SimConfig) -> ScenarioParams:
        """Draw one instance's randomized parameters (override per scenario)."""
        k1, k2, k3, k4 = jax.random.split(key, 4)
        z = jnp.zeros(())
        lambda_main = jax.random.uniform(
            k1, (cfg.n_lanes,), minval=0.15, maxval=0.55
        )
        p_cav = jax.random.uniform(k2, (), minval=0.0, maxval=1.0)
        v0_mean = jax.random.uniform(k3, (), minval=26.0, maxval=33.0)
        seed = jax.random.randint(k4, (), 0, 2**31 - 1).astype(jnp.uint32)
        return ScenarioParams(
            lambda_main=lambda_main, lambda_ramp=z, p_cav=p_cav,
            v0_mean=v0_mean, v0_ramp=v0_mean, seed=seed, aux0=z, aux1=z,
        )

    # ---------------- hook 1: longitudinal_mods ----------------

    def snapshot_ctx(self, st, cfg: SimConfig, geom: RoadGeometry):
        """Optional scenario state computed ONCE per neighborhood snapshot
        (the simulator builds tables twice per step: pre-move and
        post-change) and passed to every ``longitudinal_mods`` call on that
        snapshot — e.g. the ring's per-lane rearmost-vehicle scan, which
        would otherwise be recomputed for each MOBIL candidate query."""
        return None

    def longitudinal_mods(self, st, cfg: SimConfig, geom: RoadGeometry,
                          sp: ScenarioParams, query_lane, nb, a, ctx=None):
        """Extra accel constraints (pre-clamp). ``nb`` is the Neighbors
        answer for ``query_lane`` (lead/follower indices, gaps, masks);
        ``ctx`` is this snapshot's ``snapshot_ctx`` result."""
        return a

    # ---------------- hook 2: lateral_rules ----------------

    def mobil_eligible(self, st, cfg: SimConfig, geom: RoadGeometry):
        """Vehicles allowed discretionary MOBIL changes (activity and
        cooldown are layered on by the simulator)."""
        return st.lane < geom.n_lanes

    def mobil_candidate_ok(self, st, cfg: SimConfig, geom: RoadGeometry,
                           cand_lane):
        """Per-vehicle mask: may MOBIL move this vehicle into
        ``cand_lane[i]``? Scenario veto of illegal targets (e.g. a closing
        lane) — applied inside the MOBIL decision, so a vetoed move neither
        consumes the lane-change cooldown nor counts as a lane change."""
        return jnp.ones_like(st.active)

    def lateral_rules(self, st, cfg: SimConfig, geom: RoadGeometry,
                      sp: ScenarioParams, tabs, mobil_lane):
        """Mandatory scenario moves after MOBIL. ``st.lane`` is still the
        pre-MOBIL lane; ``mobil_lane`` is MOBIL's proposal. Returns the
        final lane vector and the count of scenario-forced moves (the
        ``merges_ok`` metric delta)."""
        return mobil_lane, jnp.zeros((), jnp.int32)

    # ---------------- hook 3: boundary ----------------

    def boundary_spawn(self, cfg: SimConfig, geom: RoadGeometry,
                       sp: ScenarioParams):
        """Demand process: (arrival rate, base desired speed, lane id) per
        spawn lane. The lane count must be static per scenario."""
        lanes = jnp.arange(geom.n_lanes)
        base_v0 = jnp.full((geom.n_lanes,), 1.0) * sp.v0_mean
        return sp.lambda_main, base_v0, lanes

    def boundary_route(self, cfg: SimConfig, geom: RoadGeometry,
                       sp: ScenarioParams, key: jax.Array, lanes):
        """Route of an arrival in each spawn lane of ``lanes`` (0 through,
        1 exit), drawn from ``key``; ``None``: the scenario has no routes,
        draws nothing and leaves ``SimState.route`` at 0."""
        return None

    def boundary_clamp(self, st, cfg: SimConfig, geom: RoadGeometry,
                       pos, vel):
        """Post-integration position/velocity rules (walls, ring wrap)."""
        return pos, vel

    def boundary_exit(self, st, cfg: SimConfig, geom: RoadGeometry):
        """Exit predicate on the post-integration state."""
        return st.active & (st.pos > geom.road_len)

    def boundary_missed(self, st, cfg: SimConfig, geom: RoadGeometry,
                        exited):
        """Vehicles among ``exited`` that leave by the wrong way for their
        route (the ``exits_missed`` metric delta); none without routes."""
        return jnp.zeros((), jnp.int32)

    def boundary_gauge(self, st, cfg: SimConfig, geom: RoadGeometry):
        """Scenario congestion gauge (vehicle-steps this step); reported as
        the ``ramp_blocked_steps`` metric field, renamed per
        ``metric_aliases``."""
        return jnp.zeros((), jnp.int32)
