"""Vectorized mixed-traffic simulator core — the Webots+SUMO analogue.

The paper runs a Webots front-end puppeteered by SUMO (§2.5.3) as its sample
workload: a mixed-traffic highway merge. Porting that to TPU means replacing
the process-per-instance binary simulator with a pure-JAX physics step:

- **IDM** (Intelligent Driver Model, Treiber et al. 2000) longitudinal
  car-following — what SUMO's default Krauss model approximates.
- **MOBIL** (Kesting et al. 2007) incentive/safety lane changing.
- **Pluggable scenarios**: everything workload-specific (road geometry,
  demand, the merge's gap acceptance, a lane-drop's forced exit, a ring
  road's wrap...) lives behind the Scenario API (``repro.core.scenarios``).
  ``sim_step`` itself is scenario-agnostic: it calls the scenario's three
  jit hook groups — ``longitudinal_mods``, ``lateral_rules``, ``boundary``
  — selected by the static ``SimConfig.scenario`` name, so new workloads
  never fork the physics step.

One instance = one row of a batched state pytree: ``vmap`` gives the paper's
"n simulation instances per node" and sharding the instance axis gives "across
n nodes" — both collapse into one SPMD program (DESIGN.md §2).

Shapes are static (fixed ``n_slots`` vehicle capacity, active-masking), so the
whole rollout jit-compiles into a single ``lax.scan``.

The neighbor search + IDM evaluation is the physics hot spot. All per-step
neighborhood queries (own-lane IDM, the four MOBIL candidate searches, the
ramp-merge target search, the post-lane-change recompute, and the
collision/TTC check — historically ~8 independent O(N²) scans) now route
through the **neighborhood engine** (``repro.core.neighbors``), selected by
``SimConfig.neighbor_impl``:

- ``"reference"`` — the original per-query masked all-pairs scans (parity
  oracle; slowest).
- ``"dense"``     — fused dense path: one ``[N,N]`` pairwise
  materialization per state snapshot, per-lane tables derived in a single
  batched reduction; every query becomes an O(N) gather.
- ``"sort"``      — O(N log N): stable per-lane argsorts of positions per
  snapshot, queries answered by searchsorted adjacency.
- ``"pallas"``    — the generalized multi-query TPU kernel
  (``repro.kernels.idm.neighbor_kernel``; interpret mode off-TPU).
- ``"auto"``      — the default: ``dense`` on a TPU up to 2048 slots,
  ``sort`` above that and on CPU hosts
  (``repro.core.neighbors.resolve_impl``).

``sim_step`` performs exactly **two** neighborhood constructions per step:
one for the pre-move snapshot (serving the own-lane, MOBIL and merge
queries via lane tables) and one for the post-lane-change snapshot (the
integration accel). The collision/TTC stage reuses the post-change lead
assignment with post-integration positions instead of running a third scan:
each vehicle is checked against the leader it was actually following during
the dt, which is equivalent up to within-step overtakes (< dt·Δv ≈ cm scale)
and preserves the crash-on-overlap invariant.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.scenario import (
    SimConfig,
    ScenarioParams,
    driver_params,
)
from repro.core.scenarios import get_scenario
from repro.core.trace import LANE_CHANGE, LONGITUDINAL, RECORD, SPAWN
from repro.core.scenarios.base import (  # noqa: F401  (idm_accel re-exported)
    RoadGeometry,
    Scenario,
    idm_accel,
)
from repro.core.neighbors import (  # noqa: F401  (neighbor_info re-exported)
    Neighbors,
    NeighborTables,
    build_tables,
    neighbor_info,
    query_lanes,
)

INF = 1e9


class SimState(NamedTuple):
    pos: jax.Array        # [N] f32, meters from segment start
    vel: jax.Array        # [N] f32, m/s
    lane: jax.Array       # [N] i32; n_lanes == ramp lane
    active: jax.Array     # [N] bool
    is_cav: jax.Array     # [N] bool
    v0: jax.Array         # [N] f32 desired speed
    T: jax.Array          # [N] f32 headway
    a_max: jax.Array      # [N] f32
    b_comf: jax.Array     # [N] f32
    s0: jax.Array         # [N] f32
    politeness: jax.Array # [N] f32
    cooldown: jax.Array   # [N] i32 lane-change cooldown
    route: jax.Array      # [N] i32 0 through, 1 exit (Scenario.boundary_route)
    key: jax.Array        # PRNG key
    t: jax.Array          # [] i32 step counter


class SimMetrics(NamedTuple):
    throughput: jax.Array      # [] i32 vehicles exited
    spawned: jax.Array         # [] i32
    speed_sum: jax.Array       # [] f32
    speed_count: jax.Array     # [] f32
    collisions: jax.Array      # [] i32
    merges_ok: jax.Array       # [] i32 scenario-forced lane moves (merges)
    ramp_blocked_steps: jax.Array  # [] i32 scenario congestion gauge
    # (field names keep their merge-era spelling: the struct must be
    # identical across scenarios for lax.switch sweeps; scenarios rename
    # them in records via Scenario.metric_aliases)
    lane_changes: jax.Array    # [] i32
    min_ttc: jax.Array         # [] f32
    steps: jax.Array           # [] i32
    exits_missed: jax.Array    # [] i32 route failures (boundary_missed)

    @staticmethod
    def zeros() -> "SimMetrics":
        z_i = jnp.zeros((), jnp.int32)
        z_f = jnp.zeros((), jnp.float32)
        return SimMetrics(z_i, z_i, z_f, z_f, z_i, z_i, z_i, z_i,
                          jnp.asarray(INF, jnp.float32), z_i, z_i)


def init_state(cfg: SimConfig, key: jax.Array) -> SimState:
    """Empty world: every one of the ``cfg.n_slots`` vehicle slots inactive.

    Positions park at ``-INF`` meters (off-road sentinel), speeds at 0 m/s,
    driver parameters at their population means; ``key`` seeds the
    instance's in-sim PRNG stream (spawns, driver draws). The step counter
    ``t`` starts at 0 — horizons and trace-row indices are absolute step
    counts from here.
    """
    n = cfg.n_slots
    zf = jnp.zeros((n,), jnp.float32)
    return SimState(
        pos=zf - INF,
        vel=zf,
        lane=jnp.zeros((n,), jnp.int32),
        active=jnp.zeros((n,), bool),
        is_cav=jnp.zeros((n,), bool),
        v0=zf + 30.0,
        T=zf + 1.5,
        a_max=zf + 1.4,
        b_comf=zf + 2.0,
        s0=zf + 2.0,
        politeness=zf + 0.3,
        cooldown=jnp.zeros((n,), jnp.int32),
        route=jnp.zeros((n,), jnp.int32),
        key=key,
        t=jnp.zeros((), jnp.int32),
    )


# --------------------------------------------------------------------------
# physics primitives (idm_accel lives in scenarios.base; re-exported above)
# --------------------------------------------------------------------------

def _own_accel(st: SimState, cfg: SimConfig, geom: RoadGeometry,
               scn: Scenario, sp: ScenarioParams, query_lane, nb: Neighbors,
               ctx=None):
    """IDM accel of each vehicle against its lead in ``query_lane``, plus
    the scenario's extra longitudinal constraints (ramp wall, speed-limit
    zone, wrap-around leader, ...), clamped to ``[-b_max, a_max]``.
    ``ctx`` is the scenario's once-per-snapshot ``snapshot_ctx`` result."""
    v_lead = jnp.where(nb.has_lead, st.vel[nb.lead_idx], 0.0)
    gap = jnp.where(nb.has_lead, nb.lead_gap, INF)
    dv = jnp.where(nb.has_lead, st.vel - v_lead, 0.0)
    a = idm_accel(st.vel, dv, gap, st.v0, st.T, st.a_max, st.b_comf, st.s0)
    a = scn.longitudinal_mods(st, cfg, geom, sp, query_lane, nb, a, ctx)
    return jnp.clip(a, -cfg.b_max, st.a_max)


# --------------------------------------------------------------------------
# MOBIL lane changing (scenario gates eligibility + mandatory moves)
# --------------------------------------------------------------------------

def _mobil_candidate(st: SimState, cfg: SimConfig, geom: RoadGeometry,
                     scn: Scenario, sp: ScenarioParams, a_now,
                     own: Neighbors, tabs: NeighborTables, cand_lane,
                     ctx=None):
    """MOBIL incentive + safety for moving every vehicle to ``cand_lane[i]``.

    ``own`` is the current-lane neighborhood (lead for the old-follower
    gap, follower as MOBIL's vehicle k); ``tabs`` answers the candidate-lane
    query — no per-candidate O(N²) scans.
    """
    nb = tabs.query(cand_lane)
    li, lg, hl, fi, fg, hf = nb
    # self in target lane
    a_new = _own_accel(st, cfg, geom, scn, sp, cand_lane, nb, ctx)

    # new follower j: before = its current accel; after = following self
    a_j_before = jnp.where(hf, a_now[fi], 0.0)
    gap_j_after = jnp.where(hf, fg, INF)
    a_j_after = idm_accel(
        st.vel[fi], st.vel[fi] - st.vel, gap_j_after,
        st.v0[fi], st.T[fi], st.a_max[fi], st.b_comf[fi], st.s0[fi],
    )
    a_j_after = jnp.where(hf, a_j_after, 0.0)

    # old follower k: before = its current accel (following self);
    # after = following self's current lead
    ki, hk = own.foll_idx, own.has_foll
    lead_pos = jnp.where(own.has_lead, st.pos[own.lead_idx], INF)
    lead_vel = jnp.where(own.has_lead, st.vel[own.lead_idx], 0.0)
    gap_k_after = lead_pos[jnp.arange(st.pos.shape[0])] - st.pos[ki] - cfg.vehicle_len
    a_k_before = jnp.where(hk, a_now[ki], 0.0)
    a_k_after = idm_accel(
        st.vel[ki], st.vel[ki] - lead_vel, gap_k_after,
        st.v0[ki], st.T[ki], st.a_max[ki], st.b_comf[ki], st.s0[ki],
    )
    a_k_after = jnp.where(hk, a_k_after, 0.0)

    incentive = (a_new - a_now) + st.politeness * (
        (a_j_after - a_j_before) + (a_k_after - a_k_before)
    )
    safe = (a_j_after >= -cfg.b_safe) & (
        jnp.where(hf, fg, INF) > 0.0
    ) & (jnp.where(hl, lg, INF) > 0.0)
    return incentive, safe


def _apply_lane_changes(st: SimState, cfg: SimConfig, geom: RoadGeometry,
                        scn: Scenario, sp: ScenarioParams, a_now,
                        own: Neighbors, tabs: NeighborTables, ctx=None):
    """Simultaneous MOBIL decisions for scenario-eligible vehicles."""
    eligible = scn.mobil_eligible(st, cfg, geom) & st.active
    can_change = eligible & (st.cooldown == 0)

    left = jnp.minimum(st.lane + 1, geom.n_lanes - 1)
    right = jnp.maximum(st.lane - 1, 0)
    inc_l, safe_l = _mobil_candidate(st, cfg, geom, scn, sp, a_now, own,
                                     tabs, left, ctx)
    inc_r, safe_r = _mobil_candidate(st, cfg, geom, scn, sp, a_now, own,
                                     tabs, right, ctx)
    ok_l = (safe_l & (inc_l > cfg.mobil_athr) & (left != st.lane)
            & can_change & scn.mobil_candidate_ok(st, cfg, geom, left))
    ok_r = (safe_r & (inc_r > cfg.mobil_athr) & (right != st.lane)
            & can_change & scn.mobil_candidate_ok(st, cfg, geom, right))

    go_left = ok_l & (~ok_r | (inc_l >= inc_r))
    go_right = ok_r & ~go_left
    new_lane = jnp.where(go_left, left, jnp.where(go_right, right, st.lane))
    changed = go_left | go_right
    cooldown = jnp.where(
        changed, cfg.lane_change_cooldown, jnp.maximum(st.cooldown - 1, 0)
    )
    return new_lane, cooldown, jnp.sum(changed.astype(jnp.int32))


# --------------------------------------------------------------------------
# spawning — the demand process (per-instance randomized rates; the
# scenario's boundary_spawn hook decides WHICH lanes spawn at WHAT rates)
# --------------------------------------------------------------------------

def _spawn(st: SimState, cfg: SimConfig, geom: RoadGeometry, scn: Scenario,
           sp: ScenarioParams, key: jax.Array):
    """Bernoulli(λ·dt) arrivals per spawn lane; claims free slots with fresh
    drivers.

    Fully vectorized over the scenario's spawn lanes: one uniform block
    for every per-lane draw and a rank-based free-slot allocation, instead
    of the historical Python loop (~17 tiny PRNG/scatter ops per step —
    the dominant per-step cost at small ``n_slots``). At most one vehicle
    spawns per lane per step; each arriving lane claims the next-lowest
    free slot in lane order, exactly like the sequential loop did.
    """
    n = st.pos.shape[0]
    lam, base_v0, lanes = scn.boundary_spawn(cfg, geom, sp)
    n_spawn_lanes = lanes.shape[0]                   # static per scenario
    ku, kj = jax.random.split(key)
    u = jax.random.uniform(ku, (3, n_spawn_lanes))   # arrival, cav, v0 jitter

    arrive = u[0] < lam * cfg.dt                                   # [L]
    # headway check at the spawn point, all lanes at once
    in_lane = st.active[None, :] & (st.lane[None, :] == lanes[:, None])
    nearest = jnp.min(jnp.where(in_lane, st.pos[None, :], INF), axis=1)
    clear = nearest > cfg.spawn_gap
    if geom.ring:
        # on a closed road traffic also approaches the spawn point from
        # behind, across the seam, possibly at full speed — demand braking
        # headroom behind the seam before injecting a fresh vehicle
        rear_gap = geom.road_len - jnp.max(
            jnp.where(in_lane, st.pos[None, :], -INF), axis=1
        )
        clear = clear & (rear_gap > 3.0 * cfg.spawn_gap)

    # rank-based slot claim: the r-th lane that wants to spawn takes the
    # r-th-lowest free slot; lanes beyond the free-slot count miss out
    free = ~st.active
    n_free = jnp.sum(free.astype(jnp.int32))
    want = arrive & clear
    rank = jnp.cumsum(want.astype(jnp.int32)) - want.astype(jnp.int32)
    ok = want & (rank < n_free)
    free_slots = jnp.argsort(~free, stable=True)     # free indices first
    slot = jnp.where(ok, free_slots[jnp.minimum(rank, n - 1)], n)  # n = drop

    cav = u[1] < sp.p_cav
    new_v0 = base_v0 * (0.9 + 0.2 * u[2])
    dp = driver_params(cav, kj, n_spawn_lanes)
    # split(key) gave fold_in(key, 0) and fold_in(key, 1): the route's key
    # is the next one, so it moves no other draw
    route = scn.boundary_route(cfg, geom, sp, jax.random.fold_in(key, 2),
                               lanes)
    # headway-derived entry speed uses the NEW driver's just-drawn headway
    # (the slot may still hold a previous occupant's stale T)
    init_v = jnp.minimum(new_v0, nearest / jnp.maximum(dp["T"], 0.5))

    def put(arr, val):
        return arr.at[slot].set(val.astype(arr.dtype), mode="drop")

    st = st._replace(
        pos=put(st.pos, jnp.zeros_like(new_v0)),
        vel=put(st.vel, jnp.maximum(init_v * 0.8, 5.0)),
        lane=put(st.lane, lanes),
        active=put(st.active, jnp.ones_like(cav)),
        is_cav=put(st.is_cav, cav),
        v0=put(st.v0, new_v0),
        T=put(st.T, dp["T"]),
        a_max=put(st.a_max, dp["a_max"]),
        b_comf=put(st.b_comf, dp["b_comf"]),
        s0=put(st.s0, dp["s0"]),
        politeness=put(st.politeness, dp["politeness"]),
    )
    if route is not None:
        st = st._replace(route=put(st.route, route))
    return st, jnp.sum(ok.astype(jnp.int32))


# --------------------------------------------------------------------------
# one physics step
# --------------------------------------------------------------------------

def sim_step(
    st: SimState, cfg: SimConfig, sp: ScenarioParams
) -> tuple[SimState, SimMetrics]:
    """One dt step of ``cfg.scenario``. Returns the new state and this
    step's metric deltas. Scenario-specific physics enters only through the
    scenario's hooks — this function never special-cases a workload."""
    scn = get_scenario(cfg.scenario)
    geom = scn.geometry(cfg)
    key, k_spawn = jax.random.split(st.key)
    st = st._replace(key=key)
    impl = cfg.neighbor_impl
    n_lanes_total = geom.n_lanes_total

    # Device phases are named scopes (``repro.core.trace.PHASES``): the
    # neighbour engine names its own builds and queries ``neighbors``;
    # this step names ``longitudinal``, ``lane_change`` and ``spawn``.

    # 1. pre-move snapshot: ONE fused neighborhood pass serves the own-lane
    #    accel, both MOBIL candidate evaluations and the scenario's
    #    lateral-rule queries (merge target, drop target, ...)
    tabs = build_tables(
        st.pos, st.lane, st.active, cfg.vehicle_len, n_lanes_total, impl
    )
    own = tabs.query(st.lane)
    with jax.named_scope(LONGITUDINAL):
        ctx = scn.snapshot_ctx(st, cfg, geom)
        a_now = _own_accel(st, cfg, geom, scn, sp, st.lane, own, ctx)

    # 2. lane changes: discretionary MOBIL, then the scenario's mandatory
    #    moves (gap-acceptance merge, forced lane-drop exit, vetoes)
    with jax.named_scope(LANE_CHANGE):
        new_lane, cooldown, n_lc = _apply_lane_changes(
            st, cfg, geom, scn, sp, a_now, own, tabs, ctx
        )
        new_lane, n_forced = scn.lateral_rules(st, cfg, geom, sp, tabs,
                                               new_lane)
    st = st._replace(lane=new_lane, cooldown=cooldown)

    # 3. post-change snapshot (second and last construction): recompute
    #    accel on post-change lanes, integrate, apply boundary clamps
    nb = query_lanes(
        st.pos, st.lane, st.active, cfg.vehicle_len, st.lane, impl,
        n_lanes_total=n_lanes_total,
    )
    with jax.named_scope(LONGITUDINAL):
        # lanes changed: fresh snapshot
        ctx2 = scn.snapshot_ctx(st, cfg, geom)
        accel = _own_accel(st, cfg, geom, scn, sp, st.lane, nb, ctx2)
        accel = jnp.where(st.active, accel, 0.0)
        vel = jnp.maximum(st.vel + accel * cfg.dt, 0.0)
        pos = st.pos + vel * cfg.dt
        pos, vel = scn.boundary_clamp(st, cfg, geom, pos, vel)
        st = st._replace(pos=pos, vel=vel)

        # 4. collisions: follower overlapping its lead → remove follower.
        #    Reuses the post-change lead assignment with the integrated
        #    positions (each vehicle vs the leader it followed during this
        #    dt) instead of a third all-pairs construction. On a ring the
        #    gap is measured with a centered wrap so a leader crossing the
        #    seam is not a phantom collision.
        li2, hl2 = nb.lead_idx, nb.has_lead
        dgap = st.pos[li2] - st.pos
        if geom.ring:
            half = 0.5 * geom.road_len
            dgap = jnp.mod(dgap + half, geom.road_len) - half
        lg2 = jnp.where(
            hl2, dgap - cfg.vehicle_len, INF - cfg.vehicle_len
        )
        crashed = st.active & hl2 & (lg2 < 0.0)
        n_crash = jnp.sum(crashed.astype(jnp.int32))

        # 5. exits (scenario predicate; a ring has none), and the route
        #    failures among them, before the exits leave
        exited = scn.boundary_exit(st, cfg, geom)
        n_out = jnp.sum(exited.astype(jnp.int32))
        n_missed = scn.boundary_missed(st, cfg, geom, exited)
        active = st.active & ~exited & ~crashed
        st = st._replace(active=active, pos=jnp.where(active, st.pos, -INF))

        # 6. TTC (closing pairs only)
        dv = jnp.where(hl2, st.vel - st.vel[li2], 0.0)
        ttc = jnp.where(
            st.active & hl2 & (dv > 0.1), jnp.maximum(lg2, 0.0) / dv, INF
        )
        min_ttc = jnp.min(ttc)

        # 7. scenario congestion gauge (ramp blockage, drop blockage,
        #    stopped vehicles, zone occupancy — reported in the
        #    ramp_blocked_steps slot)
        n_blocked = scn.boundary_gauge(st, cfg, geom)

    # 8. demand (scenario decides spawn lanes/rates)
    with jax.named_scope(SPAWN):
        st, n_spawn = _spawn(st, cfg, geom, scn, sp, k_spawn)
    st = st._replace(t=st.t + 1)

    delta = SimMetrics(
        throughput=n_out,
        spawned=n_spawn,
        speed_sum=jnp.sum(jnp.where(st.active, st.vel, 0.0)),
        speed_count=jnp.sum(st.active.astype(jnp.float32)),
        collisions=n_crash,
        merges_ok=n_forced,
        ramp_blocked_steps=n_blocked,
        lane_changes=n_lc,
        min_ttc=min_ttc,
        steps=jnp.ones((), jnp.int32),
        exits_missed=n_missed,
    )
    return st, delta


def _acc(m: SimMetrics, d: SimMetrics) -> SimMetrics:
    return SimMetrics(
        throughput=m.throughput + d.throughput,
        spawned=m.spawned + d.spawned,
        speed_sum=m.speed_sum + d.speed_sum,
        speed_count=m.speed_count + d.speed_count,
        collisions=m.collisions + d.collisions,
        merges_ok=m.merges_ok + d.merges_ok,
        ramp_blocked_steps=m.ramp_blocked_steps + d.ramp_blocked_steps,
        lane_changes=m.lane_changes + d.lane_changes,
        min_ttc=jnp.minimum(m.min_ttc, d.min_ttc),
        steps=m.steps + d.steps,
        exits_missed=m.exits_missed + d.exits_missed,
    )


# --------------------------------------------------------------------------
# rollouts
# --------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("cfg", "n_steps", "rec"))
def rollout_chunk_rec(
    st: SimState,
    metrics: SimMetrics,
    sp: ScenarioParams,
    horizon: jax.Array,
    trace,
    cfg: SimConfig,
    n_steps: int,
    rec=None,
):
    """Advance ``n_steps`` (one walltime slice). Steps past ``horizon`` no-op.

    The per-instance ``horizon`` makes instances genuinely variable-cost —
    the straggler population the sweep scheduler must handle (DESIGN.md §7).

    With a :class:`repro.core.record.RecordConfig` ``rec`` (static), the
    rollout also fills ``trace`` (a :class:`repro.core.record.TraceBuffer`):
    rows are indexed by absolute step count, so recording is invariant to
    chunk boundaries and idempotent under re-execution (fault revert,
    checkpoint resume). With ``rec=None``, ``trace`` must be None and rides
    through untouched.

    Recording cost: when ``n_steps`` is a multiple of the stride, the scan
    is two-level — an outer scan over stride windows whose inner scan is
    the plain physics loop — so ALL recording work (channel extraction +
    buffer writes) runs once per window, not once per step. This relies on
    live instances entering a chunk at a stride-aligned step count, which
    every sweep path guarantees (``t`` only ever advances in whole chunks,
    and ``SweepConfig`` chunking makes chunk boundaries stride-aligned
    whenever this fast path is selected). Otherwise a per-step fallback
    records at identical bit-for-bit rows at ~1 extra write per step.
    """
    from repro.core.record import record_step  # deferred: no import cycle

    def step_body(carry, _):
        st, m = carry
        live = st.t < horizon
        st2, d = sim_step(st, cfg, sp)
        m2 = _acc(m, d)
        st = jax.tree.map(lambda a, b: jnp.where(live, b, a), st, st2)
        m = jax.tree.map(lambda a, b: jnp.where(live, b, a), m, m2)
        return (st, m), None

    if rec is None:
        (st, metrics), _ = jax.lax.scan(
            step_body, (st, metrics), None, length=n_steps
        )
        return st, metrics, trace

    stride = rec.record_every
    if n_steps % stride == 0:
        # fast path: record once per stride window (see docstring)
        def window(carry, _):
            st, m, tr = carry
            t0 = st.t
            (st, m), _ = jax.lax.scan(step_body, (st, m), None, length=stride)
            # an instance frozen at its horizon for the whole window must
            # not re-emit its final row every subsequent window
            with jax.named_scope(RECORD):
                tr = record_step(tr, st, m, rec, st.t > t0)
            return (st, m, tr), None

        (st, metrics, trace), _ = jax.lax.scan(
            window, (st, metrics, trace), None, length=n_steps // stride
        )
        return st, metrics, trace

    def body(carry, _):
        st, m, tr = carry
        live = st.t < horizon
        st2, d = sim_step(st, cfg, sp)
        m2 = _acc(m, d)
        # off-stride and not-live writes drop; live re-writes after a
        # revert reproduce identical rows (determinism)
        with jax.named_scope(RECORD):
            tr = record_step(tr, st2, m2, rec, live)
        st = jax.tree.map(lambda a, b: jnp.where(live, b, a), st, st2)
        m = jax.tree.map(lambda a, b: jnp.where(live, b, a), m, m2)
        return (st, m, tr), None

    (st, metrics, trace), _ = jax.lax.scan(
        body, (st, metrics, trace), None, length=n_steps
    )
    return st, metrics, trace


def rollout_chunk(
    st: SimState,
    metrics: SimMetrics,
    sp: ScenarioParams,
    horizon: jax.Array,
    cfg: SimConfig,
    n_steps: int,
) -> tuple[SimState, SimMetrics]:
    """Recording-free chunk rollout (see :func:`rollout_chunk_rec`)."""
    st, metrics, _ = rollout_chunk_rec(
        st, metrics, sp, horizon, None, cfg, n_steps, None
    )
    return st, metrics


def rollout(
    key: jax.Array, cfg: SimConfig, sp: ScenarioParams, n_steps: int
) -> SimMetrics:
    """Full single-instance episode from a fresh world."""
    st = init_state(cfg, key)
    horizon = jnp.asarray(n_steps, jnp.int32)
    _, metrics = rollout_chunk(
        st, SimMetrics.zeros(), sp, horizon, cfg, n_steps
    )
    return metrics
