"""Scenario configuration + randomized parameter sampling.

The paper randomizes each simulation instance's traffic demand by re-running
SUMO's ``duarouter`` with a fresh ``$RANDOM`` seed before every run (Appendix
B). Here every instance's demand, driver mix and driver parameters are drawn
from a per-instance PRNG key (``jax.random.fold_in(sweep_key, instance_id)``),
which gives the same property — thousands of runs with meaningful deviations —
with exact reproducibility and no shared mutable state (the TPU-native fix for
the paper's duplicate-TraCI-port bug class).

*Which* simulation runs is no longer baked in here: ``SimConfig.scenario``
names an entry in the scenario registry (:mod:`repro.core.scenarios`), and
``sample_scenario_params`` dispatches to that scenario's ``sample_params``.
The paper's Phase-II workload is the default, ``"highway_merge"``::

      lane 2  ──────────────────────────────────────────▶
      lane 1  ──────────────────────────────────────────▶
      lane 0  ──────────────────────────────────────────▶
      ramp(3) ════════════╗ merge zone ╔═══ (ends; must merge or stop)
                      merge_start   merge_end

See ``repro.core.scenarios`` for the catalog (lane_drop, stop_and_go,
speed_limit_zone, ...) and for how to register a custom scenario.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import NamedTuple

import jax
import jax.numpy as jnp


@dataclass(frozen=True)
class SimConfig:
    """Static (compile-time) simulator configuration."""

    n_slots: int = 64          # fixed vehicle capacity per instance
    n_lanes: int = 3           # main highway lanes (ramp is lane index n_lanes)
    road_len: float = 1000.0
    # generic scenario-zone extents; the highway merge reads them as the
    # merge zone, lane_drop as the bottleneck taper, speed_limit_zone as
    # the work zone (see each scenario's geometry())
    merge_start: float = 600.0
    merge_end: float = 750.0
    scenario: str = "highway_merge"  # registry name (repro.core.scenarios)
    dt: float = 0.1            # SUMO default step length
    vehicle_len: float = 4.5
    spawn_gap: float = 15.0    # min headway at the spawn point
    # IDM bounds
    b_safe: float = 4.0        # MOBIL safety decel limit
    b_max: float = 8.0         # emergency decel clamp
    mobil_athr: float = 0.1    # MOBIL incentive threshold
    lane_change_cooldown: int = 20  # steps between lane changes
    # merge gap acceptance
    merge_gap_front: float = 8.0
    merge_gap_rear: float = 10.0
    record_every: int = 0      # 0 = no trajectory recording
    # neighborhood engine implementation (repro.core.neighbors):
    # "reference" (per-query O(N²) scans, the parity oracle), "dense"
    # (fused single-pass O(N²)), "sort" (O(N log N) argsort+searchsorted),
    # "pallas" (the multi-query TPU kernel; interpret mode off-TPU). All
    # four are bit-for-bit equivalent (tests/test_neighbors.py). "auto"
    # picks from n_slots and the backend (neighbors.resolve_impl): dense on
    # a TPU up to 2048 slots (it beat sort at every n_slots measured
    # there), sort above that and on CPU hosts, where sort was the fastest
    # in one-instance rollouts at every measured n_slots.
    neighbor_impl: str = "auto"


class ScenarioParams(NamedTuple):
    """Per-instance randomized demand + driver-population parameters.

    Every field is a scalar (or per-lane vector) jnp array so a batch of
    instances is just a vmapped axis. The structure is shared by *all*
    registered scenarios (a ``lax.switch`` over scenario step functions needs
    one common pytree): fields a scenario does not use are sampled as zeros,
    and ``aux0``/``aux1`` are generic scenario knobs (speed-limit value,
    perturbation strength, ... — see each scenario's ``sample_params``).
    """

    lambda_main: jax.Array   # [n_lanes] arrival rate veh/s per main lane
    lambda_ramp: jax.Array   # [] arrival rate on the ramp (ramp scenarios)
    p_cav: jax.Array         # [] CAV penetration (paper: mixed traffic)
    v0_mean: jax.Array       # [] mean desired speed
    v0_ramp: jax.Array       # [] desired speed on ramp
    seed: jax.Array          # [] uint32 instance seed (for in-sim draws)
    aux0: jax.Array = 0.0    # [] scenario-specific knob (see scenario doc)
    aux1: jax.Array = 0.0    # [] scenario-specific knob


def sample_scenario_params(key: jax.Array, cfg: SimConfig) -> ScenarioParams:
    """Draw one instance's parameters for ``cfg.scenario`` (registry dispatch)."""
    from repro.core.scenarios import get_scenario  # deferred: avoids cycle

    return get_scenario(cfg.scenario).sample_params(key, cfg)


# Driver-type parameter tables (human, CAV). CAVs run tighter headways and
# react harder — the standard mixed-traffic assumption in the CAV-merge
# literature the paper's Phase II targets.
HUMAN = dict(T=1.5, a_max=1.4, b_comf=2.0, s0=2.0, politeness=0.3)
CAV = dict(T=0.9, a_max=2.0, b_comf=2.5, s0=1.5, politeness=0.5)


def driver_params(is_cav: jax.Array, jitter_key: jax.Array, n: int):
    """Per-vehicle IDM/MOBIL parameters given the CAV mask, with human jitter."""
    jt = jax.random.uniform(jitter_key, (n,), minval=0.85, maxval=1.15)

    def mix(h: float, c: float) -> jax.Array:
        base = jnp.where(is_cav, c, h)
        # humans get parameter jitter, CAVs are standardized
        return jnp.where(is_cav, base, base * jt)

    return dict(
        T=mix(HUMAN["T"], CAV["T"]),
        a_max=mix(HUMAN["a_max"], CAV["a_max"]),
        b_comf=mix(HUMAN["b_comf"], CAV["b_comf"]),
        s0=mix(HUMAN["s0"], CAV["s0"]),
        politeness=jnp.where(is_cav, CAV["politeness"], HUMAN["politeness"]),
    )
