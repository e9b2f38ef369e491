"""Neighborhood engine parity: every implementation must match the
``neighbor_info`` oracle bit-for-bit, and ``sim_step`` must be trajectory-
identical across ``SimConfig.neighbor_impl`` settings."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import SimConfig, init_state, sample_scenario_params
from repro.core.neighbors import (
    CHOICES,
    IMPLS,
    TPU_DENSE_MAX_SLOTS,
    _check_impl,
    build_tables,
    neighbor_info,
    query_lanes,
    resolve_impl,
)
from repro.core.simulator import sim_step

L = 4  # 3 main lanes + ramp
# (slot count, lane tables): the merge's 3 + 1 lanes, and the US-101
# weave's 5 + 1 (ids keep the slot count alone for 3 + 1)
SIZES = [pytest.param(n, L, id=str(n)) for n in (8, 16, 48, 200)] + [
    pytest.param(n, 6, id=f"{n}x6") for n in (48, 200)]
FIELDS = ("lead_idx", "lead_gap", "has_lead", "foll_idx", "foll_gap",
          "has_foll")


def _rand_world(key, n, p_act=0.8, lanes=L):
    """Random world with forced exact position ties (the argmin/stable-sort
    tie-break edge case) and inactive slots."""
    ks = jax.random.split(key, 3)
    pos = jax.random.uniform(ks[0], (n,), jnp.float32, 0.0, 900.0)
    lane = jax.random.randint(ks[1], (n,), 0, lanes)
    if n > 4:
        pos = pos.at[1].set(pos[0]).at[4].set(pos[0])
        lane = lane.at[1].set(lane[0]).at[4].set(lane[0])
    active = jax.random.uniform(ks[2], (n,)) < p_act
    return pos, lane, active


def _impl_kwargs(impl):
    return {"interpret": True} if impl == "pallas" else {}


@pytest.mark.parametrize("impl", [i for i in CHOICES if i != "reference"])
@pytest.mark.parametrize("n,lanes", SIZES)
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_tables_match_oracle_bitwise(impl, n, lanes, seed):
    pos, lane, active = _rand_world(jax.random.key(seed * 1000 + n), n,
                                    lanes=lanes)
    tabs = build_tables(pos, lane, active, 4.5, lanes, impl,
                        **_impl_kwargs(impl))
    for l in range(lanes):
        q = jnp.full((n,), l, jnp.int32)
        ref = neighbor_info(pos, lane, active, 4.5, q)
        got = tabs.query(q)
        for name, a, b in zip(FIELDS, ref, got):
            np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b),
                err_msg=f"{impl} lane={l} field={name}",
            )


@pytest.mark.parametrize("impl", list(CHOICES))
@pytest.mark.parametrize("seed", [0, 1])
def test_query_lanes_match_oracle_bitwise(impl, seed):
    n = 64
    pos, lane, active = _rand_world(jax.random.key(seed + 77), n)
    qv = jax.random.randint(jax.random.key(seed + 123), (n,), 0, L)
    ref = neighbor_info(pos, lane, active, 4.5, qv)
    got = query_lanes(pos, lane, active, 4.5, qv, impl, n_lanes_total=L,
                      **_impl_kwargs(impl))
    for name, a, b in zip(FIELDS, ref, got):
        np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b), err_msg=f"{impl} field={name}"
        )


def test_tables_on_live_simulator_states():
    """Parity on organically-evolved worlds (spawns, merges, exits)."""
    cfg = SimConfig(n_slots=32)
    sp = sample_scenario_params(jax.random.key(1), cfg)
    st = init_state(cfg, jax.random.key(0))
    step = jax.jit(lambda s: sim_step(s, cfg, sp))
    for _ in range(150):
        st, _ = step(st)
    nl = cfg.n_lanes + 1
    ref = build_tables(st.pos, st.lane, st.active, cfg.vehicle_len, nl,
                       "reference")
    for impl in ("dense", "sort", "pallas"):
        got = build_tables(st.pos, st.lane, st.active, cfg.vehicle_len, nl,
                           impl, **_impl_kwargs(impl))
        for name, a, b in zip(FIELDS, ref, got):
            np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b), err_msg=f"{impl} {name}"
            )


def test_unknown_impl_raises():
    pos, lane, active = _rand_world(jax.random.key(0), 8)
    _check_impl("auto")
    with pytest.raises(ValueError, match="neighbor_impl"):
        _check_impl("quadtree")
    with pytest.raises(ValueError, match="neighbor_impl"):
        build_tables(pos, lane, active, 4.5, L, "quadtree")
    with pytest.raises(ValueError, match="neighbor_impl"):
        query_lanes(pos, lane, active, 4.5, lane, "quadtree",
                    n_lanes_total=L)
    with pytest.raises(ValueError, match="neighbor_impl"):
        resolve_impl("quadtree", 64, "tpu")


# The table as measured on a TPU v5e (PERF.md): dense up to 2048 slots
# there, sort above it and on every other backend.
@pytest.mark.parametrize("impl,n_slots,backend,want", [
    ("auto", 16, "tpu", "dense"),
    ("auto", 64, "tpu", "dense"),
    ("auto", 256, "tpu", "dense"),
    ("auto", 2048, "tpu", "dense"),
    ("auto", 2049, "tpu", "sort"),
    ("auto", 4096, "tpu", "sort"),
    ("auto", 64, "cpu", "sort"),
    ("auto", 256, "cpu", "sort"),
    ("auto", 64, "gpu", "sort"),
    *[(impl, n, b, impl) for impl in IMPLS for n in (64, 4096)
      for b in ("tpu", "cpu")],
])
def test_resolve_impl_crossover_table(impl, n_slots, backend, want):
    assert TPU_DENSE_MAX_SLOTS == 2048
    assert resolve_impl(impl, n_slots, backend) == want


@pytest.mark.parametrize("n,engine", [(64, "dense"), (2049, "sort")])
def test_auto_on_tpu_traces_the_resolved_engine(monkeypatch, n, engine):
    """On a TPU ``auto`` traces exactly the engine it resolves to, and the
    post-change recompute on ``dense`` stays one [N,N] scan (no table)."""
    pos, lane, active = _rand_world(jax.random.key(3), n)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def jaxprs(impl):
        tabs = jax.make_jaxpr(lambda p, l, a: build_tables(
            p, l, a, 4.5, L, impl))(pos, lane, active)
        one = jax.make_jaxpr(lambda p, l, a: query_lanes(
            p, l, a, 4.5, l, impl, n_lanes_total=L))(pos, lane, active)
        return str(tabs), str(one)

    auto, pinned = jaxprs("auto"), jaxprs(engine)
    assert auto == pinned
    assert ("sort[" in auto[0]) == (engine == "sort")
    if engine == "dense":
        assert auto[1] == str(jax.make_jaxpr(
            lambda p, l, a: neighbor_info(p, l, a, 4.5, l))(pos, lane, active))


# the US-101 weave's geometry (bench/configs/us101.json): 5 + 1 lane tables
WEAVE = dict(scenario="us101_weave", n_lanes=5, road_len=640.0,
             merge_start=60.0, merge_end=420.0, discharge_speed=4.0)


@pytest.mark.parametrize("n_slots,world", [
    pytest.param(16, {}, id="16"), pytest.param(48, {}, id="48"),
    pytest.param(48, WEAVE, id="48-us101_weave")])
def test_sim_step_equivalent_across_impls(n_slots, world):
    """End-to-end: identical trajectories for every neighbor_impl."""
    base = SimConfig(n_slots=n_slots, **world)
    sp = sample_scenario_params(jax.random.key(1), base)
    finals = {}
    for impl in IMPLS:
        cfg = dataclasses.replace(base, neighbor_impl=impl)
        st = init_state(cfg, jax.random.key(0))
        step = jax.jit(lambda s, cfg=cfg: sim_step(s, cfg, sp))
        for _ in range(100):
            st, _ = step(st)
        finals[impl] = jax.device_get(
            st._replace(key=jax.random.key_data(st.key))
        )
    ref = finals["reference"]
    for impl, st in finals.items():
        for name, a, b in zip(ref._fields, ref, st):
            np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b), err_msg=f"{impl} {name}"
            )
    assert np.asarray(ref.active).sum() > 0  # the worlds actually populated
