"""Scenario registry — the workload catalog behind ``SimConfig.scenario``.

Built-in catalog (name → geometry → hooks exercised → headline metrics):

================== ======================= ========================== =====================
name               geometry                hooks                      scenario metrics
================== ======================= ========================== =====================
highway_merge      3 lanes + on-ramp       long. wall, forced merge,  merges_ok,
                   merge zone              ramp clamp, blockage gauge ramp_blocked_steps
lane_drop          3 lanes, lane 0 ends    long. wall, forced merge   forced_merges,
                   (bottleneck taper)      + MOBIL veto, drop clamp   drop_blocked_steps
stop_and_go        ring road (wraps)       wrap follow, periodic      stopped_steps,
                                           brake pulse, no exits      min_ttc
speed_limit_zone   straight pipe,          zone accel cap +           zone_veh_steps,
                   work zone               anticipatory braking       throughput
us101_weave        5 lanes + auxiliary     route draw, route caps +   weave_moves,
                   lane, on-ramp to        MOBIL veto, forced moves   exits_missed,
                   off-ramp gore (weave)   both ways, gore exit,      aux_blocked_steps
                                           discharge limit
================== ======================= ========================== =====================

Registering a custom scenario::

    from repro.core.scenarios import Scenario, register_scenario

    @register_scenario
    class MyScenario(Scenario):
        name = "my_scenario"
        def geometry(self, cfg): ...
        def sample_params(self, key, cfg): ...
        # override whichever of the three hook groups the workload needs

then run it with ``SimConfig(scenario="my_scenario")`` or
``python -m repro.launch.sweep --scenario my_scenario``.

The registry order is stable (insertion order); ``scenario_index`` gives a
scenario's registry-order integer id (useful for labeling datasets). Note
that mixed sweeps select ``lax.switch`` branches by position in the sweep's
own roster (``SweepConfig.scenarios`` / ``SweepState.scenario_id``), which
matches the registry index only when the roster is the full registry in
registration order.
"""

from __future__ import annotations

from repro.core.scenarios.base import (
    INF,
    RoadGeometry,
    Scenario,
    idm_accel,
)

_REGISTRY: dict[str, Scenario] = {}


def register_scenario(cls: type[Scenario]) -> type[Scenario]:
    """Class decorator: instantiate + register a scenario under ``cls.name``."""
    inst = cls()
    if not inst.name or inst.name == "base":
        raise ValueError(f"{cls.__name__} must set a unique `name`")
    if inst.name in _REGISTRY:
        raise ValueError(f"scenario {inst.name!r} already registered")
    _REGISTRY[inst.name] = inst
    return cls


def get_scenario(name: str) -> Scenario:
    """Look up a registered :class:`Scenario` singleton by registry name.

    Raises ``KeyError`` (listing the registered names) for unknown names —
    the error surface for every ``SimConfig.scenario`` typo.
    """
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(
            f"unknown scenario {name!r}; registered: {list(_REGISTRY)}"
        ) from None


def list_scenarios() -> list[str]:
    """Registered scenario names, in stable registration order."""
    return list(_REGISTRY)


def scenario_index(name: str) -> int:
    """Registry-order integer id of a registered scenario (stable label).

    NOT the mixed-sweep branch selector: ``SweepState.scenario_id`` indexes
    the sweep's roster (``SweepConfig.scenarios``), not the registry.
    """
    get_scenario(name)
    return list(_REGISTRY).index(name)


# ---- built-in catalog (import order defines the stable ids) --------------

from repro.core.scenarios.highway_merge import HighwayMerge
from repro.core.scenarios.lane_drop import LaneDrop
from repro.core.scenarios.stop_and_go import StopAndGo
from repro.core.scenarios.speed_limit_zone import SpeedLimitZone
from repro.core.scenarios.us101_weave import US101Weave

register_scenario(HighwayMerge)
register_scenario(LaneDrop)
register_scenario(StopAndGo)
register_scenario(SpeedLimitZone)
register_scenario(US101Weave)

__all__ = [
    "INF",
    "RoadGeometry",
    "Scenario",
    "idm_accel",
    "register_scenario",
    "get_scenario",
    "list_scenarios",
    "scenario_index",
    "HighwayMerge",
    "LaneDrop",
    "StopAndGo",
    "SpeedLimitZone",
    "US101Weave",
]
