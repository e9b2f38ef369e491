"""Single-pass neighborhood engine — the simulator's O(N²) hot spot, fused.

Every simulated vehicle-step needs "who is ahead of / behind me in lane q"
for several query lanes q: the own-lane IDM search, four searches inside the
two MOBIL candidate evaluations, the ramp-merge target search, the post-
lane-change recompute, and the collision/TTC check — historically ~8
independent O(N²) masked all-pairs scans per ``sim_step``.

This module answers all of them through one API with four interchangeable
implementations (``SimConfig.neighbor_impl``), or ``auto`` (the default),
which picks one from what the call can see (:func:`resolve_impl`):

``reference``
    The original per-query masked all-pairs scan (``neighbor_info``), one
    O(N²) pass per lane table. Kept as the bit-for-bit parity oracle.
``dense``
    Fused dense path: materializes the pairwise ``dpos``/activity masks
    **once** per state snapshot and derives the per-lane lead/follower
    tables for all lanes in one batched ``[L, N, N]`` reduction.
``sort``
    O(L·N log N) path: one stable per-lane argsort of positions per
    snapshot (L = lane count, a small constant); every query is answered
    by ``searchsorted`` adjacency lookups in the sorted lane segments.
``pallas``
    TPU Pallas kernel (``repro.kernels.idm.neighbor_kernel``): a multi-query
    lead+follower search with VMEM-resident running minima, gridded over
    (query, ego-tile, other-tile). Interpret mode is auto-enabled off-TPU.

All implementations share one contract (the seed ``neighbor_info``
semantics, bit-for-bit):

- lead  = argmin over vehicles strictly ahead  (``pos_j > pos_i``) in q;
- foll  = argmin over vehicles strictly behind (``pos_j < pos_i``) in q;
- exact position ties (including self) are neither lead nor follower;
- index ties resolve to the lowest slot index (stable/first-minimum);
- absent neighbors report ``idx = 0``, ``gap = INF - veh_len``,
  ``has = False``; inactive queriers have no neighbors.

The engine exposes **per-lane tables**: for every lane ``l ∈ [0, L)`` and
every vehicle ``i``, the lead/follower of ``i`` *as if it were in lane l*.
Arbitrary per-vehicle query-lane vectors then become O(N) gathers, so one
table build serves every pre-move query of a step.

``auto`` resolves from the static slot count ``N`` and the backend the
program compiles for: ``dense`` on a TPU up to ``TPU_DENSE_MAX_SLOTS``
slots, ``sort`` everywhere else. On a TPU v5e ``sort``'s ``searchsorted``
binary searches are ``while`` loops of gathers, and the dense reduction
beats them at every slot count measured there. CPU hosts keep ``sort``,
the fastest there in one-instance rollouts at every measured ``N``
(``benchmarks/throughput.py`` times the engines per slot count;
``PERF.md`` holds the chip table).

Every build and query runs under the ``neighbors`` named scope
(``repro.core.trace.NEIGHBORS``), so a device profile attributes the
engine's ops to it wherever it is called.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.trace import NEIGHBORS

INF = 1e9

IMPLS = ("reference", "dense", "sort", "pallas")
CHOICES = ("auto",) + IMPLS

# Largest slot count at which ``auto`` picks ``dense`` on a TPU. This is
# the edge of the measured range, not a crossover: on a TPU v5e ``dense``
# beat ``sort`` 4.1-6.0x in per-step time at every slot count from 64 to
# 2048 (48-instance highway_merge chunks, benchmarks/throughput.py), and
# nothing above 2048 was measured. Other backends (CPU hosts) keep ``sort``
# at every slot count.
TPU_DENSE_MAX_SLOTS = 2048


class Neighbors(NamedTuple):
    """Lead/follower answer for one query-lane vector. All fields [N]."""

    lead_idx: jax.Array   # i32, 0 when has_lead is False
    lead_gap: jax.Array   # f32 bumper-to-bumper, INF - veh_len when absent
    has_lead: jax.Array   # bool
    foll_idx: jax.Array   # i32
    foll_gap: jax.Array   # f32
    has_foll: jax.Array   # bool


class NeighborTables(NamedTuple):
    """Per-lane neighbor tables. All fields [L, N] (lane-major)."""

    lead_idx: jax.Array
    lead_gap: jax.Array
    has_lead: jax.Array
    foll_idx: jax.Array
    foll_gap: jax.Array
    has_foll: jax.Array

    @jax.named_scope(NEIGHBORS)
    def query(self, query_lane: jax.Array) -> Neighbors:
        """Answer a per-vehicle query-lane vector by gathering table rows."""
        cols = jnp.arange(query_lane.shape[0])
        return Neighbors(*(t[query_lane, cols] for t in self))


def neighbor_info(pos, lane, active, veh_len, query_lane):
    """Per-vehicle lead/follower in ``query_lane[i]`` (masked O(N²) search).

    The seed implementation and parity oracle. Returns (lead_idx, lead_gap,
    has_lead, foll_idx, foll_gap, has_foll); gaps are bumper-to-bumper.
    """
    dpos = pos[None, :] - pos[:, None]                      # [i,j] = pos_j - pos_i
    n = pos.shape[0]
    eye = jnp.eye(n, dtype=bool)
    pair_ok = (
        (lane[None, :] == query_lane[:, None])
        & active[None, :]
        & active[:, None]
        & ~eye
    )
    ahead = pair_ok & (dpos > 0.0)
    behind = pair_ok & (dpos <= 0.0) & ~(dpos == 0.0)       # strictly behind

    lead_d = jnp.where(ahead, dpos, INF)
    lead_idx = jnp.argmin(lead_d, axis=1)
    lead_gap = jnp.min(lead_d, axis=1) - veh_len
    has_lead = jnp.any(ahead, axis=1)

    foll_d = jnp.where(behind, -dpos, INF)
    foll_idx = jnp.argmin(foll_d, axis=1)
    foll_gap = jnp.min(foll_d, axis=1) - veh_len
    has_foll = jnp.any(behind, axis=1)
    return lead_idx, lead_gap, has_lead, foll_idx, foll_gap, has_foll


# --------------------------------------------------------------------------
# reference impl — per-lane wrapper over neighbor_info
# --------------------------------------------------------------------------

def _reference_tables(pos, lane, active, veh_len, n_lanes_total):
    def one(l):
        q = jnp.full_like(lane, l)
        return Neighbors(*neighbor_info(pos, lane, active, veh_len, q))

    return NeighborTables(*jax.vmap(one)(jnp.arange(n_lanes_total)))


# --------------------------------------------------------------------------
# fused dense impl — one [N,N] materialization, all lanes in one reduction
# --------------------------------------------------------------------------

def _dense_tables(pos, lane, active, veh_len, n_lanes_total):
    n = pos.shape[0]
    dpos = pos[None, :] - pos[:, None]
    eye = jnp.eye(n, dtype=bool)
    pair_act = active[None, :] & active[:, None] & ~eye
    ahead_act = pair_act & (dpos > 0.0)                     # [N,N], lane-free
    behind_act = pair_act & (dpos < 0.0)
    lanes = jnp.arange(n_lanes_total, dtype=lane.dtype)
    in_lane = lane[None, :] == lanes[:, None]               # [L,N] over j

    ahead = ahead_act[None] & in_lane[:, None, :]           # [L,N,N]
    behind = behind_act[None] & in_lane[:, None, :]

    lead_d = jnp.where(ahead, dpos[None], INF)
    lead_idx = jnp.argmin(lead_d, axis=2)
    lead_gap = jnp.min(lead_d, axis=2) - veh_len
    has_lead = jnp.any(ahead, axis=2)

    foll_d = jnp.where(behind, -dpos[None], INF)
    foll_idx = jnp.argmin(foll_d, axis=2)
    foll_gap = jnp.min(foll_d, axis=2) - veh_len
    has_foll = jnp.any(behind, axis=2)
    return NeighborTables(
        lead_idx, lead_gap, has_lead, foll_idx, foll_gap, has_foll
    )


# --------------------------------------------------------------------------
# sort impl — one stable argsort per lane, searchsorted adjacency queries
# --------------------------------------------------------------------------

def _sort_tables(pos, lane, active, veh_len, n_lanes_total):
    n = pos.shape[0]
    no_gap = jnp.asarray(INF, pos.dtype) - veh_len

    def one_lane(l):
        in_l = active & (lane == l)
        key = jnp.where(in_l, pos, INF)
        order = jnp.argsort(key, stable=True)   # in-lane ascending, rest last
        spos = key[order]

        # lead: first entry strictly greater than pos_i ('right' skips ties,
        # which also excludes self and exact-tie vehicles, matching the oracle)
        j = jnp.searchsorted(spos, pos, side="right")
        jc = jnp.minimum(j, n - 1)
        cand = spos[jc]
        has_lead = (j < n) & (cand < INF * 0.5) & active
        lead_idx = jnp.where(has_lead, order[jc], 0).astype(jnp.int32)
        lead_gap = jnp.where(has_lead, cand - pos - veh_len, no_gap)

        # follower: last entry strictly less than pos_i. Among equal
        # positions the oracle's argmin picks the lowest slot index, i.e.
        # the FIRST entry of the tied group in stable sort order — so hop
        # back to the start of the predecessor's tie group.
        j2 = jnp.searchsorted(spos, pos, side="left") - 1
        cand2 = spos[jnp.maximum(j2, 0)]
        jf = jnp.searchsorted(spos, cand2, side="left")
        has_foll = (j2 >= 0) & (cand2 < INF * 0.5) & active
        foll_idx = jnp.where(has_foll, order[jf], 0).astype(jnp.int32)
        foll_gap = jnp.where(has_foll, pos - cand2 - veh_len, no_gap)
        return Neighbors(
            lead_idx, lead_gap, has_lead, foll_idx, foll_gap, has_foll
        )

    return NeighborTables(*jax.vmap(one_lane)(jnp.arange(n_lanes_total)))


# --------------------------------------------------------------------------
# pallas impl — multi-query TPU kernel (interpret mode off-TPU)
# --------------------------------------------------------------------------

def _pallas_tables(pos, lane, active, veh_len, n_lanes_total, interpret):
    from repro.kernels import neighbor_kernel

    q = jnp.broadcast_to(
        jnp.arange(n_lanes_total, dtype=lane.dtype)[:, None],
        (n_lanes_total, pos.shape[0]),
    )
    return NeighborTables(
        *neighbor_kernel(
            pos, lane, active, q, veh_len=veh_len, interpret=interpret
        )
    )


# --------------------------------------------------------------------------
# engine entry points
# --------------------------------------------------------------------------

def _check_impl(impl: str) -> None:
    if impl not in CHOICES:
        raise ValueError(
            f"neighbor_impl must be one of {CHOICES}, got {impl!r}")


def resolve_impl(impl: str, n_slots: int, backend: str) -> str:
    """The engine ``impl`` names at ``n_slots`` slots on ``backend``.

    ``auto`` becomes ``dense`` on a TPU up to ``TPU_DENSE_MAX_SLOTS``
    slots and ``sort`` above it or on any other backend; every other name
    passes through. All engines answer bit-for-bit alike, so this picks a speed,
    never a result.
    """
    _check_impl(impl)
    if impl != "auto":
        return impl
    if backend == "tpu" and n_slots <= TPU_DENSE_MAX_SLOTS:
        return "dense"
    return "sort"


@jax.named_scope(NEIGHBORS)
def build_tables(
    pos: jax.Array,
    lane: jax.Array,
    active: jax.Array,
    veh_len: float,
    n_lanes_total: int,
    impl: str = "dense",
    *,
    interpret: bool | None = None,
) -> NeighborTables:
    """Build per-lane lead/follower tables for one state snapshot.

    One call serves any number of per-vehicle query-lane vectors via
    ``tables.query(q)`` — this is the single fused pass that replaces the
    per-query O(N²) scans.
    """
    impl = resolve_impl(impl, pos.shape[-1], jax.default_backend())
    if impl == "reference":
        return _reference_tables(pos, lane, active, veh_len, n_lanes_total)
    if impl == "dense":
        return _dense_tables(pos, lane, active, veh_len, n_lanes_total)
    if impl == "sort":
        return _sort_tables(pos, lane, active, veh_len, n_lanes_total)
    return _pallas_tables(pos, lane, active, veh_len, n_lanes_total, interpret)


@jax.named_scope(NEIGHBORS)
def query_lanes(
    pos: jax.Array,
    lane: jax.Array,
    active: jax.Array,
    veh_len: float,
    query_lane: jax.Array,
    impl: str = "dense",
    *,
    n_lanes_total: int | None = None,
    interpret: bool | None = None,
) -> Neighbors:
    """Answer a single per-vehicle query-lane vector (one construction).

    Cheaper than ``build_tables`` when only one query is needed for a
    snapshot (the post-lane-change recompute).
    """
    impl = resolve_impl(impl, pos.shape[-1], jax.default_backend())
    if impl in ("reference", "dense"):
        # a single query vector IS one masked all-pairs scan either way
        return Neighbors(*neighbor_info(pos, lane, active, veh_len, query_lane))
    if impl == "sort":
        # one table build is already O(N log N); gather the requested rows
        if n_lanes_total is None:
            raise ValueError(
                "query_lanes(impl='sort') needs n_lanes_total (the lane "
                "count is a static table dimension)"
            )
        tabs = _sort_tables(pos, lane, active, veh_len, n_lanes_total)
        return tabs.query(query_lane)
    from repro.kernels import neighbor_kernel

    res = neighbor_kernel(
        pos, lane, active, query_lane[None, :], veh_len=veh_len,
        interpret=interpret,
    )
    return Neighbors(*(t[0] for t in res))
