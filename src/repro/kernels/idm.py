"""Neighborhood search (TPU Pallas) — the simulator's hot spot.

The paper's simulation engine (Webots physics + SUMO car following) reduces,
per step, to: for every vehicle find the nearest same-lane leader and
follower, then apply IDM/MOBIL. That is an O(N²) masked min-reduction — on
TPU, a tiled VPU problem.

``neighbor_kernel`` grids ``(Q, nI, nJ)`` over (query-lane vector, ego tile,
other tile). For each of Q per-vehicle query-lane vectors it returns lead
**and** follower (idx, gap, has) in one launch — the ~8 per-step O(N²)
searches of ``sim_step`` collapse into one kernel invocation per state
snapshot. Running (gap, idx) minima for both directions live in VMEM
scratch; ties resolve to the lowest slot index (strict-< running update +
first-minimum within a tile), matching the jnp oracle bit-for-bit.

Layout (what Mosaic accepts on v5e): the pair tile is ``[BJ, BI]`` with the
*other* vehicles on sublanes and the *egos* on lanes, so every reduction is
over sublanes and yields a lane-dense ``[1, BI]`` row. Other-vehicle inputs
arrive as ``[N, 1]`` columns and ego inputs as ``[1, N]`` rows, so the
kernel never reshapes a 1-D vector into a column. Per-query arrays are
``[Q, 1, N]`` with the query dim squeezed out of the block, so every block's
last two dims are ``(1, BI)`` — equal to the full dim / a multiple of 128.
Masks travel as ``int32``. Vehicle count is padded to a multiple of 8 (one
tile) or of the block size (several tiles); padded slots are inactive and
never win a minimum.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

INF = 1e9


def _neighbor_mq_kernel(
    pos_ref, act_ref, qlane_ref,                          # ego row [1, BI]
    pos_j_ref, lane_j_ref, act_j_ref,                     # other col [BJ, 1]
    li_ref, lg_ref, lh_ref, fi_ref, fg_ref, fh_ref,       # out [1, BI]
    lgap_s, lidx_s, fgap_s, fidx_s,                       # scratch [1, BI]
    *,
    veh_len: float,
    bj: int,
):
    ij = pl.program_id(2)

    @pl.when(ij == 0)
    def _init():
        lgap_s[...] = jnp.full_like(lgap_s, INF)
        lidx_s[...] = jnp.zeros_like(lidx_s)
        fgap_s[...] = jnp.full_like(fgap_s, INF)
        fidx_s[...] = jnp.zeros_like(fidx_s)

    dpos = pos_j_ref[...] - pos_ref[...]                  # [BJ, BI] = pos_j - pos_i
    ok = (
        (lane_j_ref[...] == qlane_ref[...])
        & (act_j_ref[...] != 0)
        & (act_ref[...] != 0)
    )
    rows = jax.lax.broadcasted_iota(jnp.int32, dpos.shape, 0)
    base = ij * bj

    def fold(d, gap_s, idx_s):
        tile_min = d.min(axis=0, keepdims=True)           # [1, BI]
        # first minimum: lowest row among the tile's minimizers
        tile_idx = base + jnp.where(d == tile_min, rows, bj).min(
            axis=0, keepdims=True
        )
        better = tile_min < gap_s[...]                    # ties keep lower j
        gap_s[...] = jnp.where(better, tile_min, gap_s[...])
        idx_s[...] = jnp.where(better, tile_idx, idx_s[...])

    fold(jnp.where(ok & (dpos > 0.0), dpos, INF), lgap_s, lidx_s)
    fold(jnp.where(ok & (dpos < 0.0), -dpos, INF), fgap_s, fidx_s)

    @pl.when(ij == pl.num_programs(2) - 1)
    def _finish():
        has_l = lgap_s[...] < INF * 0.5
        has_f = fgap_s[...] < INF * 0.5
        lg_ref[...] = lgap_s[...] - veh_len
        li_ref[...] = jnp.where(has_l, lidx_s[...], 0)
        lh_ref[...] = has_l.astype(jnp.int32)
        fg_ref[...] = fgap_s[...] - veh_len
        fi_ref[...] = jnp.where(has_f, fidx_s[...], 0)
        fh_ref[...] = has_f.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("veh_len", "block", "interpret"))
def neighbor_kernel(
    pos: jax.Array, lane: jax.Array, active: jax.Array,
    query_lanes: jax.Array,
    *,
    veh_len: float = 4.5,
    block: int = 128,
    interpret: bool = False,
) -> tuple[jax.Array, ...]:
    """Multi-query lead+follower search.

    ``query_lanes`` is ``[Q, N]`` (Q per-vehicle query-lane vectors).
    Returns ``(lead_idx, lead_gap, has_lead, foll_idx, foll_gap, has_foll)``,
    each ``[Q, N]``; semantics match ``repro.core.neighbors.neighbor_info``
    bit-for-bit (absent: idx 0, gap INF − veh_len, has False).
    ``block`` (a multiple of 128) is the tile edge once N exceeds it.
    """
    n = pos.shape[0]
    nq = query_lanes.shape[0]
    npad = -(-n // 8) * 8
    if npad > block:
        npad = -(-n // block) * block
    bi = bj = min(block, npad)
    pad = npad - n
    lane = lane.astype(jnp.int32)
    act = active.astype(jnp.int32)
    query_lanes = query_lanes.astype(jnp.int32)
    if pad:
        pos = jnp.pad(pos, (0, pad), constant_values=-INF)
        lane = jnp.pad(lane, (0, pad), constant_values=-1)
        act = jnp.pad(act, (0, pad))
        query_lanes = jnp.pad(query_lanes, ((0, 0), (0, pad)))

    ego_spec = pl.BlockSpec((1, bi), lambda q, i, j: (0, i))
    oth_spec = pl.BlockSpec((bj, 1), lambda q, i, j: (j, 0))
    qry_spec = pl.BlockSpec((None, 1, bi), lambda q, i, j: (q, 0, i))
    kernel = functools.partial(_neighbor_mq_kernel, veh_len=veh_len, bj=bj)
    i32 = jax.ShapeDtypeStruct((nq, 1, npad), jnp.int32)
    f32 = jax.ShapeDtypeStruct((nq, 1, npad), jnp.float32)
    li, lg, lh, fi, fg, fh = pl.pallas_call(
        kernel,
        grid=(nq, npad // bi, npad // bj),
        in_specs=[ego_spec, ego_spec, qry_spec,
                  oth_spec, oth_spec, oth_spec],
        out_specs=[qry_spec] * 6,
        out_shape=[i32, f32, i32, i32, f32, i32],
        scratch_shapes=[
            pltpu.VMEM((1, bi), jnp.float32),
            pltpu.VMEM((1, bi), jnp.int32),
            pltpu.VMEM((1, bi), jnp.float32),
            pltpu.VMEM((1, bi), jnp.int32),
        ],
        interpret=interpret,
    )(
        pos.reshape(1, npad), act.reshape(1, npad),
        query_lanes.reshape(nq, 1, npad),
        pos.reshape(npad, 1), lane.reshape(npad, 1), act.reshape(npad, 1),
    )

    def out(x):
        return x[:, 0, :n]

    return (
        out(li), out(lg), out(lh).astype(bool),
        out(fi), out(fg), out(fh).astype(bool),
    )
