"""The PBS-job-array analogue: a sharded, chunked, restartable simulation sweep.

Paper mapping (DESIGN.md §2):

- ``#PBS -J 1-N`` job array            → an ``[N, ...]`` instance axis sharded
  over every device of the mesh (`shard_map`-style data parallelism; the
  instances are independent so the hot loop has zero collectives).
- 15-minute walltime slices            → ``chunk_steps`` physics steps per
  ``run_chunk`` call; sweep state is checkpointable at every chunk boundary.
- PBS completion accounting            → a per-instance ``done`` bitmap; the
  run loop continues until completion is 100 % (the paper's §5.2 metric),
  surviving injected node failures (``repro.core.fault``).
- straggler mitigation                 → instances have per-instance horizons
  (variable cost); **compaction** re-packs unfinished instances onto all
  devices between chunks so finished slots stop burning lockstep compute.

Dispatch modes (``SweepConfig.dispatch``) — how a mixed-scenario chunk is
mapped onto compiled programs:

- ``"switch"``  — ONE compiled program: every instance runs a vmapped
  ``lax.switch`` over the scenario roster. Batching a switch executes *every*
  branch and ``select_n``'s the results, so a k-scenario mix pays up to k×
  the per-chunk step work. Kept as the single-compile fallback and as the
  parity oracle for ``grouped``.
- ``"grouped"`` — the **chunk execution planner** partitions the pending
  instances by ``scenario_id`` on the host, pads each group to the worker
  count (padding rows are drawn from already-finished instances, whose
  results are discarded), runs each group through its *per-scenario* jitted
  chunk fn (no switch — each instance executes exactly one branch), and
  scatters results back to logical slots. One compile per distinct roster
  SimConfig, cached across chunks. This is the same host-side repacking
  trick straggler compaction already uses, so the two are unified into one
  plan: compaction decides *which* instances are live, grouping decides how
  the live set is split into dense per-program batches.
- ``"auto"``    — ``grouped`` when the roster has >1 scenario, else
  ``switch`` (which for a single scenario is a direct call, no switch op).

Both modes are bit-for-bit trajectory-equivalent (tested); ``grouped``
recovers the k× redundancy on mixed sweeps (see BENCH_sweep.json ``mixed``).

Device sharding (the paper's "across an arbitrary number of computing
nodes"): given a :class:`jax.sharding.Mesh` with **D > 1** devices, the
runner stops issuing one global (or per-scenario) call and instead plans
**per-device blocks**: :func:`plan_chunk_blocks` packs the per-scenario
groups onto devices with LPT (longest-processing-time-first, the same
heuristic the paper uses to pack simulation jobs onto nodes), splitting a
group across devices only when it exceeds a device's fair share
``ceil(live / D)``. The chunk is then ONE sharded call
(``shard_map`` over the instance axis): every device receives its
``cap``-row block plus a scalar ``block_sid`` and runs a *scalar*
``lax.switch`` — an HLO conditional that executes only that device's
scenario branch at runtime — so heterogeneous scenarios run concurrently
on different devices with no cross-device communication inside the chunk
and no vmapped-switch tax. Blocks that must mix scenarios (more groups
than capacity allows) carry ``block_sid = -1`` and fall back to the
per-row vmapped switch for that block only. The host-side gather/scatter
at the chunk boundary is the only data movement, and every
:class:`SweepState` stays in logical instance order — so recording,
fault masks, checkpoints and aggregation are sharding-agnostic by
construction, and 1-device and N-device runs are bit-for-bit identical
(tests/test_sharded.py).
"""

from __future__ import annotations

import dataclasses
import functools
from dataclasses import dataclass
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.neighbors import resolve_impl
from repro.core.record import RecordConfig, TraceBuffer, batch_zeros
from repro.core.scenario import SimConfig, ScenarioParams
from repro.core.scenarios import get_scenario
from repro.core.simulator import (
    SimState,
    SimMetrics,
    init_state,
    rollout_chunk_rec,
)
from repro.core.trace import count, span

DISPATCH_MODES = ("auto", "switch", "grouped")


@dataclass(frozen=True)
class SweepConfig:
    """Static description of one sweep — the paper's batch-job submission.

    ``n_instances`` independent simulations, each running
    ``steps_per_instance`` physics steps (or its own drawn horizon when
    ``vary_horizon``), executed in ``chunk_steps``-step walltime slices.
    ``dispatch`` picks how a mixed-scenario chunk maps onto compiled
    programs: ``"switch"`` = ONE vmapped ``lax.switch`` program (every
    branch executes for every instance — up to k× step work on a
    k-scenario mix; the parity oracle), ``"grouped"`` = the chunk planner
    repacks instances per scenario into dense switch-free calls (and into
    per-device LPT blocks on a multi-device mesh), ``"auto"`` = grouped
    iff the roster is mixed. All modes are bit-for-bit
    trajectory-equivalent. ``record`` (a
    :class:`~repro.core.record.RecordConfig`) turns on the Phase-III
    trajectory channel. The config is hashable (a jit compile-time
    constant) and fully determines the sweep together with ``seed``.
    """

    n_instances: int = 48          # the paper's experiment: 6 nodes x 8 = 48
    steps_per_instance: int = 9000 # 15 sim-minutes at dt=0.1
    chunk_steps: int = 1500        # one "walltime slice"
    sim: SimConfig = SimConfig()
    seed: int = 0
    vary_horizon: bool = False     # straggler population: horizons in
    min_horizon_frac: float = 0.5  # [frac*steps, steps]
    compaction: bool = True        # straggler mitigation (see module docstring)
    # mixed-scenario sweep: when non-empty, instances are assigned these
    # registered scenarios round-robin. How the mix is executed is governed
    # by ``dispatch`` (see module docstring): "switch" runs every branch per
    # instance inside one compile (k× step work for a k-scenario mix);
    # "grouped" repacks instances per scenario into dense per-scenario
    # compiled calls. Empty mix = every instance runs sim.scenario.
    scenario_mix: tuple[str, ...] = ()
    dispatch: str = "auto"         # "switch" | "grouped" | "auto"
    # the neighborhood engine is selected per-instance-config via
    # sim.neighbor_impl (see repro.core.neighbors / launch.sweep --neighbor-impl)
    # trajectory recording (repro.core.record): None = terminal metrics only;
    # a RecordConfig makes every chunk also fill SweepState.trace — the
    # per-instance time series the Phase-III dataset pipeline shards out
    record: RecordConfig | None = None

    @property
    def scenarios(self) -> tuple[str, ...]:
        """The effective scenario roster (mix, or the single sim scenario)."""
        return tuple(self.scenario_mix) or (self.sim.scenario,)

    @property
    def effective_dispatch(self) -> str:
        """Resolve "auto": grouped pays off exactly when the roster is mixed."""
        if self.dispatch == "auto":
            return "grouped" if len(self.scenarios) > 1 else "switch"
        return self.dispatch


class SweepState(NamedTuple):
    """Checkpointable sweep state. All arrays have a leading [N] axis.

    The leading axis is always in LOGICAL instance order: the planner's
    gather/scatter repacking is confined to the inside of ``run_chunk``, so
    checkpoints, failure masks, and aggregation never see physical rows.
    """

    sim: SimState          # stacked per-instance simulator states
    metrics: SimMetrics    # stacked per-instance accumulators
    params: ScenarioParams # stacked per-instance scenario draws
    horizon: jax.Array     # [N] i32
    done: jax.Array        # [N] bool — the completion bitmap
    chunk: jax.Array       # [] i32 — walltime slices executed
    scenario_id: jax.Array # [N] i32 — index into SweepConfig.scenarios
    # recorded time series ([N]-stacked TraceBuffer) when
    # SweepConfig.record is set, else None (an empty pytree subtree, so
    # every tree.map/checkpoint/revert path handles both transparently)
    trace: TraceBuffer | None = None


@dataclass(frozen=True)
class GroupPlan:
    """One dense batch of a chunk execution plan.

    ``take[:keep]`` are the logical ids whose results are kept; rows past
    ``keep`` are padding (already-done instances when any exist — their
    rollout is a horizon-masked no-op and the results are discarded).
    """

    roster: int        # index into SweepConfig.scenarios; -1 = mixed (switch)
    take: np.ndarray   # [P] logical ids to gather, padded to worker multiple
    keep: int          # number of real (non-padding) rows
    identity: bool     # take == arange(N): gather/scatter can be skipped


def _partition_live(
    done: np.ndarray,
    scenario_ids: np.ndarray,
    *,
    grouped: bool,
    compaction: bool,
    hold: np.ndarray | None = None,
) -> tuple[np.ndarray, np.ndarray, list[tuple[int, np.ndarray]]]:
    """Shared first stage of BOTH planners (single-device group plans and
    multi-device block plans — they must never diverge, the bit-for-bit
    equivalence claims rest on it): the live set (pending instances under
    compaction, everyone otherwise), the done-pool padding source, and the
    per-roster ``(roster, ids)`` groups (one ``-1`` group when not
    grouped).

    ``hold`` (boolean [N]) excludes instances from the live set in EVERY
    mode, compaction or not — the fleet supervisor's retry-backoff and
    quarantine states (:mod:`repro.core.fleet`) ride on it, so a held
    instance is never stepped regardless of dispatch/compaction/sharding.
    Held instances are also never used as padding (padding must stay a
    masked no-op; only *done* instances qualify).
    """
    n = done.size
    mask_live = ~done if compaction else np.ones(n, bool)
    if hold is not None:
        mask_live = mask_live & ~hold
    live = np.flatnonzero(mask_live)
    pad_pool = np.flatnonzero(done)
    if grouped:
        rosters = np.unique(scenario_ids[live])
        groups = [(int(r), live[scenario_ids[live] == r]) for r in rosters]
    else:
        groups = [(-1, live)]
    return live, pad_pool, groups


def _pad_fill(pad_pool: np.ndarray, fallback: np.ndarray) -> np.ndarray:
    """The padding source both planners share: finished instances when any
    exist (so no live instance is stepped twice per chunk), else the given
    live fallback row — either way the padding rows' results are dropped
    by the keep-masked scatter."""
    return pad_pool if pad_pool.size else fallback


def _pad_group(idx: np.ndarray, pad_pool: np.ndarray, n_workers: int):
    """Pad ``idx`` to a multiple of the worker count (see :func:`_pad_fill`;
    the fallback row here is the group's own first live instance)."""
    pad = (-idx.size) % max(n_workers, 1)
    if pad == 0:
        return idx, idx.size
    fill = np.resize(_pad_fill(pad_pool, idx[:1]), pad)
    return np.concatenate([idx, fill]), idx.size


def plan_chunk(
    done: np.ndarray,
    scenario_ids: np.ndarray,
    n_workers: int,
    *,
    grouped: bool,
    compaction: bool,
    hold: np.ndarray | None = None,
) -> list[GroupPlan]:
    """Build the host-side execution plan for one chunk.

    Unifies straggler compaction and scenario grouping: ``compaction``
    selects the live set (pending instances only vs. everyone), ``grouped``
    splits the live set into one dense batch per roster entry, ``hold``
    masks instances out of the schedule entirely (retry backoff /
    quarantine — see :func:`_partition_live`). Returns an empty plan when
    nothing is pending.
    """
    n = done.size
    live, pad_pool, groups = _partition_live(
        done, scenario_ids, grouped=grouped, compaction=compaction,
        hold=hold,
    )
    if live.size == 0:
        return []
    plans = []
    for roster, idx in groups:
        take, keep = _pad_group(idx, pad_pool, n_workers)
        identity = take.size == n and keep == n and np.array_equal(
            take, np.arange(n)
        )
        plans.append(GroupPlan(roster=roster, take=take, keep=keep,
                               identity=identity))
    return plans


@dataclass(frozen=True)
class BlockPlan:
    """A device-blocked chunk execution plan — ONE sharded call per chunk.

    Device ``d`` owns rows ``take[d*cap : (d+1)*cap]`` of the gathered
    batch. ``keep`` marks the rows whose results are scattered back to
    their logical slots (padding rows — already-done instances, or a
    repeated live row when nothing has finished yet — are dropped).
    ``block_sid[d]`` is the roster index every row of device ``d``'s block
    runs (the per-device scalar ``lax.switch`` selector), or ``-1`` for a
    mixed block that falls back to the per-row vmapped switch.
    """

    take: np.ndarray       # [D*cap] logical ids (gather order)
    keep: np.ndarray       # [D*cap] bool — True where results are kept
    block_sid: np.ndarray  # [D] i32 — roster id per device block; -1 = mixed
    cap: int               # rows per device (multiple of workers_per_device)
    identity: bool         # take == arange(N), all kept: skip gather/scatter

    @property
    def n_devices(self) -> int:
        return self.block_sid.size


def plan_chunk_blocks(
    done: np.ndarray,
    scenario_ids: np.ndarray,
    n_devices: int,
    workers_per_device: int = 1,
    *,
    grouped: bool,
    compaction: bool,
    hold: np.ndarray | None = None,
) -> BlockPlan | None:
    """Pack one chunk's live instances into per-device-balanced blocks.

    The sharded analogue of :func:`plan_chunk` — instead of one global
    compaction (or one dense batch per scenario), the live set is packed
    onto ``n_devices`` device blocks by LPT, echoing the paper's node-level
    longest-job-first packing:

    1. partition live instances by scenario (when ``grouped``; otherwise a
       single roster ``-1`` group runs the vmapped-switch program),
    2. split any group larger than the fair share ``ceil(live / D)`` into
       fair-share-sized pieces (a group is split across devices ONLY when
       it cannot fit on one device — property-tested),
    3. LPT: place pieces largest-first onto the least-loaded device,
    4. ``cap`` = max device load rounded up to a ``workers_per_device``
       multiple; every block is padded to ``cap`` with already-done
       instances (whose rollout is a masked no-op and whose results are
       dropped), falling back to repeating a live row before anything has
       finished.

    A device block whose kept rows all share one scenario gets that
    roster's ``block_sid`` (scalar-switch dispatch: the device executes
    exactly one scenario branch); blocks forced to mix get ``-1`` (per-row
    vmapped switch for that block only). Returns ``None`` when nothing is
    pending. Deterministic: ties are broken by device index and roster id,
    so the same bitmap always produces the same plan.
    """
    n = done.size
    live, pad_pool, groups = _partition_live(
        done, scenario_ids, grouped=grouped, compaction=compaction,
        hold=hold,
    )
    if live.size == 0:
        return None
    d_count = max(n_devices, 1)
    wpd = max(workers_per_device, 1)
    # fair share per device; pieces never exceed it, so LPT never needs to
    # split a piece and a group spans >1 device only when it must
    fair = -(-live.size // d_count)
    pieces: list[tuple[int, np.ndarray]] = []
    for roster, idx in groups:
        for s in range(0, idx.size, fair):
            pieces.append((roster, idx[s : s + fair]))
    pieces.sort(key=lambda p: (-p[1].size, p[0]))  # LPT order, deterministic
    loads = np.zeros(d_count, np.int64)
    bins: list[list[tuple[int, np.ndarray]]] = [[] for _ in range(d_count)]
    for roster, idx in pieces:
        d = int(np.argmin(loads))  # least-loaded; argmin = lowest index tie
        bins[d].append((roster, idx))
        loads[d] += idx.size
    cap = max(int(loads.max()), 1)
    cap = -(-cap // wpd) * wpd
    take = np.empty(d_count * cap, np.int64)
    keep = np.zeros(d_count * cap, bool)
    block_sid = np.zeros(d_count, np.int32)
    fill_src = _pad_fill(pad_pool, live[:1])
    for d in range(d_count):
        ids = (
            np.concatenate([idx for _, idx in bins[d]])
            if bins[d]
            else np.empty(0, np.int64)
        )
        rosters_d = {roster for roster, _ in bins[d]}
        if len(rosters_d) == 1:
            block_sid[d] = rosters_d.pop()  # may be -1 (switch program)
        elif len(rosters_d) > 1:
            block_sid[d] = -1               # mixed block: per-row switch
        # an all-padding block runs any branch: its rows are done
        # instances whose rollout no-ops and whose results are dropped
        pad = cap - ids.size
        row = np.concatenate([ids, np.resize(fill_src, pad)]) if pad else ids
        take[d * cap : (d + 1) * cap] = row
        keep[d * cap : d * cap + ids.size] = True
    identity = bool(
        take.size == n and keep.all() and np.array_equal(take, np.arange(n))
    )
    return BlockPlan(take=take, keep=keep, block_sid=block_sid, cap=cap,
                     identity=identity)


def instance_sharding(mesh: Mesh | None):
    """The canonical sweep sharding: instance axis split over every mesh
    axis (``PartitionSpec(mesh.axis_names)``), everything else replicated.
    ``None`` mesh → ``None`` (single-device default placement)."""
    if mesh is None:
        return None
    return NamedSharding(mesh, P(mesh.axis_names))  # instance axis over all


_instance_sharding = instance_sharding  # back-compat alias


class SweepRunner:
    """Drives a sweep to 100 % completion in walltime-slice chunks.

    ``mesh`` (a 1-D device mesh, see :func:`repro.launch.mesh.make_host_mesh`)
    turns on the device-sharded executor: with D > 1 devices every chunk is
    ONE ``shard_map`` call over LPT-packed per-device blocks (module
    docstring). ``workers_per_device`` is the block-size granularity — the
    launcher's ``--workers`` flag: each device's block is padded to a
    multiple of it, and the fault injector's worker count is
    ``D * workers_per_device`` (the paper's nodes × instances-per-node).
    """

    def __init__(
        self,
        cfg: SweepConfig,
        mesh: Mesh | None = None,
        workers_per_device: int = 1,
    ) -> None:
        if cfg.dispatch not in DISPATCH_MODES:
            raise ValueError(
                f"dispatch must be one of {DISPATCH_MODES}, got {cfg.dispatch!r}"
            )
        if workers_per_device < 1:
            raise ValueError(
                f"workers_per_device must be >= 1, got {workers_per_device}"
            )
        self.cfg = cfg
        self.mesh = mesh
        self.sharding = instance_sharding(mesh)
        self.workers_per_device = workers_per_device
        self.n_devices = len(mesh.devices.flat) if mesh is not None else 1
        self.dispatch = cfg.effective_dispatch
        # one SimConfig per roster entry; every branch shares shapes, so the
        # switch path compiles a mixed sweep into a single SPMD program
        self._sims = tuple(
            dataclasses.replace(cfg.sim, scenario=s) for s in cfg.scenarios
        )
        # every chunk fn threads the trace (None when recording is off); the
        # RecordConfig is shared by all roster entries so lax.switch branches
        # return identical trees
        rec = cfg.record
        if len(self._sims) == 1:
            sim0 = self._sims[0]

            def chunk_one(st, m, sp, h, tr, sid):
                return rollout_chunk_rec(
                    st, m, sp, h, tr, sim0, cfg.chunk_steps, rec
                )
        else:
            branches = tuple(
                functools.partial(rollout_chunk_rec, cfg=s,
                                  n_steps=cfg.chunk_steps, rec=rec)
                for s in self._sims
            )

            def chunk_one(st, m, sp, h, tr, sid):
                return jax.lax.switch(sid, branches, st, m, sp, h, tr)

        self._chunk_fn = jax.jit(jax.vmap(chunk_one))
        # per-roster switch-free chunk fns for grouped dispatch, deduped by
        # SimConfig so a weighted mix (same scenario listed twice) shares one
        # compile cache entry; jit itself caches across chunks per shape
        by_sim: dict[SimConfig, Callable] = {}
        for s in self._sims:
            if s not in by_sim:
                by_sim[s] = jax.jit(jax.vmap(functools.partial(
                    rollout_chunk_rec, cfg=s, n_steps=cfg.chunk_steps, rec=rec
                )))
        self._roster_fns = tuple(by_sim[s] for s in self._sims)
        if self.n_devices > 1:
            self._build_block_fns()

    def _build_block_fns(self) -> None:
        """The D>1 executors: one ``shard_map`` program per chunk.

        Two jitted variants, compiled lazily on first use:

        - ``_block_fn_uniform`` — every device block is single-scenario:
          a per-device *scalar* ``lax.switch`` (an HLO conditional — the
          device executes only its own scenario's rollout at runtime).
        - ``_block_fn_full`` — adds the mixed-block fallback: a scalar
          ``lax.cond`` picks between the scalar switch and a per-row
          vmapped switch, so a ``block_sid = -1`` block pays the k× switch
          tax while uniform blocks on other devices don't. Only used for
          plans that actually contain a mixed block.
        """
        cfg, rec, sims = self.cfg, self.cfg.record, self._sims
        mesh = self.mesh
        branch_fns = [
            jax.vmap(functools.partial(
                rollout_chunk_rec, cfg=s, n_steps=cfg.chunk_steps, rec=rec
            ))
            for s in sims
        ]
        row_branches = tuple(
            functools.partial(rollout_chunk_rec, cfg=s,
                              n_steps=cfg.chunk_steps, rec=rec)
            for s in sims
        )

        def uniform(ops, block_sid):
            if len(branch_fns) == 1:
                return branch_fns[0](*ops)
            return jax.lax.switch(jnp.maximum(block_sid, 0), branch_fns, *ops)

        def mixed(ops, row_sid):
            st, m, sp, h, tr = ops
            return jax.vmap(
                lambda st, m, sp, h, tr, sid: jax.lax.switch(
                    sid, row_branches, st, m, sp, h, tr
                )
            )(st, m, sp, h, tr, row_sid)

        def block_uniform(st, m, sp, h, tr, row_sid, block_sid):
            return uniform((st, m, sp, h, tr), block_sid[0])

        def block_full(st, m, sp, h, tr, row_sid, block_sid):
            ops = (st, m, sp, h, tr)
            return jax.lax.cond(
                block_sid[0] >= 0,
                lambda o: uniform(o, block_sid[0]),
                lambda o: mixed(o, row_sid),
                ops,
            )

        spec = P(mesh.axis_names)
        wrap = lambda f: jax.jit(jax.shard_map(  # noqa: E731
            f, mesh=mesh, in_specs=spec, out_specs=spec
        ))
        self._block_fn_uniform = wrap(block_uniform)
        self._block_fn_full = (
            wrap(block_full) if len(sims) > 1 else self._block_fn_uniform
        )

    # ---------------- init ----------------

    def init(self) -> SweepState:
        cfg = self.cfg
        sims = self._sims
        base = jax.random.key(cfg.seed)

        def init_one(i):
            k = jax.random.fold_in(base, i)
            sid = jnp.asarray(i % len(sims), jnp.int32)
            k_sp = jax.random.fold_in(k, 1)
            if len(sims) == 1:
                sp = get_scenario(sims[0].scenario).sample_params(k_sp, sims[0])
            else:
                sp = jax.lax.switch(
                    sid,
                    tuple(
                        functools.partial(get_scenario(s.scenario).sample_params,
                                          cfg=s)
                        for s in sims
                    ),
                    k_sp,
                )
            st = init_state(cfg.sim, jax.random.fold_in(k, 2))
            if cfg.vary_horizon:
                frac = jax.random.uniform(
                    jax.random.fold_in(k, 3), (),
                    minval=cfg.min_horizon_frac, maxval=1.0,
                )
                horizon = (frac * cfg.steps_per_instance).astype(jnp.int32)
            else:
                horizon = jnp.asarray(cfg.steps_per_instance, jnp.int32)
            return st, SimMetrics.zeros(), sp, horizon, sid

        ids = jnp.arange(cfg.n_instances)
        sim, metrics, params, horizon, sids = jax.jit(jax.vmap(init_one))(ids)
        trace = (
            batch_zeros(cfg.record, cfg.steps_per_instance, cfg.n_instances)
            if cfg.record is not None
            else None
        )
        state = SweepState(
            sim=sim,
            metrics=metrics,
            params=params,
            horizon=horizon,
            done=jnp.zeros((cfg.n_instances,), bool),
            chunk=jnp.zeros((), jnp.int32),
            scenario_id=sids,
            trace=trace,
        )
        return self._place(state)

    def _place(self, state: SweepState) -> SweepState:
        """Shard the resting [N] state over the mesh when N divides evenly.

        Otherwise the logical-order state stays on default placement — the
        per-chunk gathered batch (always ``D*cap`` rows) is what actually
        gets sharded for compute (:meth:`_run_block`), so an indivisible
        instance count costs one extra host-side repack, never correctness.
        """
        if self.sharding is None or self.cfg.n_instances % self.n_devices:
            return state
        shard = self.sharding

        def put(x):
            if getattr(x, "ndim", 0) >= 1 and x.shape[0] == self.cfg.n_instances:
                return jax.device_put(x, shard)
            return x

        return jax.tree.map(put, state)

    def _n_workers(self) -> int:
        """Total worker slots: mesh devices × per-device instances.

        The fault injector and the planner's padding granularity both key
        on this — the paper's ``nodes × instances-per-node`` (6 × 8 = 48).
        """
        return self.n_devices * self.workers_per_device

    # ---------------- one walltime slice ----------------

    def _host_bitmap(self, state: SweepState) -> tuple[np.ndarray, np.ndarray]:
        """Pull (done, scenario_id) to host and validate the assignment.

        The planner partitions on the state's own assignment (not an
        assumed round-robin) so grouped dispatch honors whatever
        scenario_id a restored or hand-built state carries, like the
        switch program does — except that lax.switch silently clamps
        out-of-range ids; here that would mean stepping an instance with
        the wrong scenario's physics, so reject it loudly (it only happens
        on config drift at restore time).
        """
        with span("sweep.sync"):
            done, sids = jax.device_get((state.done, state.scenario_id))
        done, sids = np.asarray(done), np.asarray(sids)
        if sids.size and (sids.min() < 0 or sids.max() >= len(self._sims)):
            raise ValueError(
                f"state.scenario_id out of range for a {len(self._sims)}-"
                f"entry roster {self.cfg.scenarios} — was this state "
                "restored from a sweep with a different scenario_mix?"
            )
        return done, sids

    def plan_chunk(
        self, state: SweepState, hold: np.ndarray | None = None
    ) -> list[GroupPlan]:
        """The (single-device) chunk execution plan for the current bitmap."""
        cfg = self.cfg
        grouped = self.dispatch == "grouped"
        no_hold = hold is None or not hold.any()
        if not cfg.compaction and not grouped and no_hold:
            # full-width switch program: no repacking needed
            n = cfg.n_instances
            return [GroupPlan(roster=-1, take=np.arange(n), keep=n,
                              identity=True)]
        done, sids = self._host_bitmap(state)
        with span("sweep.plan"):
            return plan_chunk(done, sids, self._n_workers(),
                              grouped=grouped, compaction=cfg.compaction,
                              hold=hold)

    def plan_chunk_sharded(
        self, state: SweepState, hold: np.ndarray | None = None
    ) -> BlockPlan | None:
        """The D>1 plan: per-device LPT blocks (:func:`plan_chunk_blocks`)."""
        done, sids = self._host_bitmap(state)
        with span("sweep.plan"):
            return plan_chunk_blocks(
                done, sids, self.n_devices, self.workers_per_device,
                grouped=self.dispatch == "grouped",
                compaction=self.cfg.compaction,
                hold=hold,
            )

    def run_chunk(
        self, state: SweepState, hold: np.ndarray | None = None
    ) -> SweepState:
        """Advance every pending instance by one walltime slice.

        Dispatch is asynchronous: the returned state's arrays are futures
        the devices are still computing — callers only block when they
        read them (``jax.device_get`` / ``block_until_ready``), which is
        what the pipelined run loop exploits to overlap host I/O with
        device compute (:func:`repro.core.fault.run_with_failures`).

        ``hold`` (boolean [N]) keeps the masked instances off this chunk's
        schedule — their state is untouched and the chunk counter still
        advances, which is how the fleet supervisor implements retry
        backoff and quarantine (:mod:`repro.core.fleet`). A chunk whose
        live set is empty (everything done, quarantined or held) is a
        counter-only no-op.
        """
        with span("sweep.chunk"):
            if self.n_devices > 1:
                bp = self.plan_chunk_sharded(state, hold)
                if bp is not None:
                    state = self._run_block(state, bp)
            else:
                for plan in self.plan_chunk(state, hold):
                    state = self._run_group(state, plan)
            done = state.sim.t >= state.horizon
            return state._replace(done=done, chunk=state.chunk + 1)

    def _count_slot_steps(self, rows: int) -> None:
        """Counter ``sweep.slot_steps``: what one group or block computes,
        with ``impl`` the neighbour engine that computes it."""
        sim = self.cfg.sim
        count("sweep.slot_steps", rows * self.cfg.chunk_steps * sim.n_slots,
              impl=resolve_impl(sim.neighbor_impl, sim.n_slots,
                                jax.default_backend()))

    def _run_block(self, state: SweepState, bp: BlockPlan) -> SweepState:
        """Gather per-device blocks, run ONE sharded call, scatter back.

        The gather + explicit ``device_put`` onto the instance sharding is
        the chunk's only data movement; inside the ``shard_map`` call each
        device steps its own rows with zero collectives.
        """
        self._count_slot_steps(bp.take.size)
        with span("sweep.gather"):
            take = jnp.asarray(bp.take)
            if bp.identity:
                sub = (state.sim, state.metrics, state.params, state.horizon,
                       state.trace)
                row_sid = state.scenario_id
            else:
                sub = jax.tree.map(
                    lambda x: x[take],
                    (state.sim, state.metrics, state.params, state.horizon,
                     state.trace),
                )
                row_sid = state.scenario_id[take]
            sub = jax.device_put(sub, self.sharding)
            row_sid = jax.device_put(row_sid, self.sharding)
            bsid = jax.device_put(jnp.asarray(bp.block_sid), self.sharding)
        fn = (
            self._block_fn_full
            if (bp.block_sid < 0).any()
            else self._block_fn_uniform
        )
        with span("sweep.step"):
            sim, metrics, trace = fn(*sub, row_sid, bsid)
        if bp.identity:
            return state._replace(sim=sim, metrics=metrics, trace=trace)
        with span("sweep.scatter"):
            kept = jnp.asarray(np.flatnonzero(bp.keep))
            upd = jnp.asarray(bp.take[bp.keep])

            def scatter(full, part):
                return full.at[upd].set(part[kept])

            return state._replace(
                sim=jax.tree.map(scatter, state.sim, sim),
                metrics=jax.tree.map(scatter, state.metrics, metrics),
                trace=jax.tree.map(scatter, state.trace, trace),
            )

    def _run_group(self, state: SweepState, plan: GroupPlan) -> SweepState:
        """Gather one plan group, step it, scatter results to logical slots.

        The trace buffer rides the same gather/scatter as sim/metrics
        (``state.trace`` is None when recording is off — an empty subtree
        every tree.map here passes through untouched), which is what makes
        recording dispatch-agnostic by construction.
        """
        fn = self._chunk_fn if plan.roster < 0 else self._roster_fns[plan.roster]
        self._count_slot_steps(plan.take.size)
        if plan.identity:
            args = (state.sim, state.metrics, state.params, state.horizon,
                    state.trace)
            with span("sweep.step"):
                sim, metrics, trace = (
                    fn(*args, state.scenario_id) if plan.roster < 0
                    else fn(*args)
                )
            return state._replace(sim=sim, metrics=metrics, trace=trace)
        with span("sweep.gather"):
            take = jnp.asarray(plan.take)
            sub = jax.tree.map(
                lambda x: x[take],
                (state.sim, state.metrics, state.params, state.horizon,
                 state.trace),
            )
            if plan.roster < 0:
                sub = (*sub, state.scenario_id[take])
        with span("sweep.step"):
            sim, metrics, trace = fn(*sub)
        with span("sweep.scatter"):
            # drop padding rows, scatter results back to logical slots
            keep = plan.keep
            upd = jnp.asarray(plan.take[:keep])

            def scatter(full, part):
                return full.at[upd].set(part[:keep])

            return state._replace(
                sim=jax.tree.map(scatter, state.sim, sim),
                metrics=jax.tree.map(scatter, state.metrics, metrics),
                trace=jax.tree.map(scatter, state.trace, trace),
            )

    # ---------------- full run with fault handling ----------------

    def run(
        self,
        state: SweepState | None = None,
        max_chunks: int = 10_000,
        on_chunk: Callable[[int, SweepState], SweepState] | None = None,
    ) -> SweepState:
        """Run until the completion bitmap is all-true.

        ``on_chunk(chunk_idx, state) -> state`` is the fault-injection /
        checkpoint hook: it may revert instances (simulated node failure) or
        persist state. The loop re-schedules whatever remains incomplete —
        completion always reaches 100 % (paper §5.2).
        """
        if state is None:
            state = self.init()
        for c in range(max_chunks):
            if bool(jax.device_get(jnp.all(state.done))):
                break
            state = self.run_chunk(state)
            if on_chunk is not None:
                state = on_chunk(c, state)
        return state

    # ---------------- elastic re-meshing ----------------

    def remesh(self, state: SweepState, mesh: Mesh | None) -> SweepState:
        """Move a sweep onto a different mesh (elastic scale up/down).

        Logical state is untouched — only placement and the block
        executors change — so a checkpoint taken on N devices resumes on
        M devices bit-for-bit (tests/test_sharded.py).
        """
        self.mesh = mesh
        self.sharding = instance_sharding(mesh)
        self.n_devices = len(mesh.devices.flat) if mesh is not None else 1
        if self.n_devices > 1:
            self._build_block_fns()
        return self._place(state)


def completion_rate(state: SweepState) -> float:
    return float(jax.device_get(jnp.mean(state.done.astype(jnp.float32))))
