"""Big-data output aggregation (paper §2.10).

The paper's pipeline exists to aggregate thousands of per-run output datasets
into one large dataset for ML (Phase III). Here a finished sweep's stacked
:class:`SimMetrics` *is* that dataset; this module turns it into per-instance
records and population summaries (the quantities the Phase-III models learn
to predict: throughput, merge success, safety).

Scenario awareness: pass the sweep's ``scenario_id`` vector and roster
(``SweepConfig.scenarios``) and records gain a ``scenario`` field plus the
scenario's *aliased* metric names (``Scenario.metric_aliases`` — e.g. the
``ramp_blocked_steps`` slot surfaces as ``stopped_steps`` for a ring road),
while summaries gain a ``per_scenario`` group-by.
"""

from __future__ import annotations

from typing import Any, Sequence

import jax
import numpy as np

from repro.core.simulator import SimMetrics
from repro.core.scenario import ScenarioParams
from repro.core.scenarios import get_scenario


# columnar layout shared by metrics_to_columns / metrics_to_records and the
# shard writer — ordered exactly as records have always been keyed
_METRIC_COLUMNS = (
    "throughput", "spawned", "mean_speed", "collisions", "merges_ok",
    "ramp_blocked_steps", "lane_changes", "min_ttc", "steps", "exits_missed",
)
_PARAM_COLUMNS = (
    "lambda_main", "lambda_ramp", "p_cav", "v0_mean", "aux0", "aux1",
)


def _bcast(x: np.ndarray, n: int) -> np.ndarray:
    """Per-instance column even when a param leaf was sampled as a scalar."""
    x = np.asarray(x)
    return np.broadcast_to(x, (n,) + x.shape[1:]) if x.ndim else np.full(n, x)


def metrics_to_columns(
    metrics: SimMetrics,
    params: ScenarioParams | None = None,
    scenario_ids: Any = None,
    scenario_names: Sequence[str] | None = None,
) -> dict[str, np.ndarray]:
    """Stacked [N] metrics → columnar numpy dataset (fully vectorized).

    This is the dataset-writer's native layout (one array per field, no
    per-instance Python) and the engine under :func:`metrics_to_records`.
    Integer columns come out i64, float columns f32/f64; ``lambda_main`` is
    the one 2-D column ([N, n_lanes]).
    """
    m = jax.tree.map(lambda x: np.asarray(jax.device_get(x)), metrics)
    n = m.throughput.shape[0]
    cols: dict[str, np.ndarray] = {"instance": np.arange(n, dtype=np.int64)}
    cols["throughput"] = m.throughput.astype(np.int64)
    cols["spawned"] = m.spawned.astype(np.int64)
    cols["mean_speed"] = (
        m.speed_sum / np.maximum(m.speed_count, 1.0)
    ).astype(np.float64)
    cols["collisions"] = m.collisions.astype(np.int64)
    cols["merges_ok"] = m.merges_ok.astype(np.int64)
    cols["ramp_blocked_steps"] = m.ramp_blocked_steps.astype(np.int64)
    cols["lane_changes"] = m.lane_changes.astype(np.int64)
    cols["min_ttc"] = m.min_ttc.astype(np.float64)
    cols["steps"] = m.steps.astype(np.int64)
    cols["exits_missed"] = m.exits_missed.astype(np.int64)
    if scenario_ids is not None and scenario_names is not None:
        ids = np.asarray(jax.device_get(scenario_ids)).astype(np.int64)
        cols["scenario_id"] = ids
        cols["scenario"] = np.asarray(scenario_names, dtype=object)[ids]
    if params is not None:
        p = jax.tree.map(lambda x: np.asarray(jax.device_get(x)), params)
        cols["lambda_main"] = _bcast(p.lambda_main, n).astype(np.float64)
        for name in ("lambda_ramp", "p_cav", "v0_mean", "aux0", "aux1"):
            cols[name] = _bcast(getattr(p, name), n).astype(np.float64)
    return cols


def metrics_to_records(
    metrics: SimMetrics,
    params: ScenarioParams | None = None,
    scenario_ids: Any = None,
    scenario_names: Sequence[str] | None = None,
) -> list[dict[str, Any]]:
    """Stacked [N] metrics → list of per-instance dict records.

    Built on :func:`metrics_to_columns`: every numeric conversion happens
    as one bulk ``.tolist()`` per column instead of the historical
    per-instance ``int()``/``float()`` calls (which dominated at N≥10k).
    The dict-per-instance output shape and key order are unchanged.
    """
    return records_from_columns(
        metrics_to_columns(metrics, params, scenario_ids, scenario_names)
    )


def records_from_columns(cols: dict[str, np.ndarray]) -> list[dict[str, Any]]:
    """:func:`metrics_to_columns` output → per-instance dict records (for
    callers that already built the columns, e.g. the shard writer)."""
    n = cols["instance"].shape[0]
    has_scenario = "scenario" in cols
    has_params = "lambda_main" in cols
    # bulk-convert to Python scalars/lists once per column
    base_keys = ("instance",) + _METRIC_COLUMNS
    lists = {k: cols[k].tolist() for k in base_keys}
    if has_scenario:
        names = cols["scenario"].tolist()
        aliases = {
            name: get_scenario(name).metric_aliases
            for name in dict.fromkeys(names)
        }
    if has_params:
        lists.update({k: cols[k].tolist() for k in _PARAM_COLUMNS})
    records: list[dict[str, Any]] = []
    for i in range(n):
        rec = {k: lists[k][i] for k in base_keys}
        if has_scenario:
            rec["scenario"] = names[i]
            # surface the scenario's meaning of the generic metric slots
            for generic, alias in aliases[names[i]].items():
                rec[alias] = rec[generic]
        if has_params:
            for k in _PARAM_COLUMNS:
                rec[k] = lists[k][i]
        records.append(rec)
    return records


def _summarize(m: SimMetrics, sel: np.ndarray) -> dict[str, float]:
    speed = m.speed_sum[sel] / np.maximum(m.speed_count[sel], 1.0)
    total_steps = float(m.steps[sel].sum())
    return {
        "instances": int(sel.sum()),
        "total_throughput": int(m.throughput[sel].sum()),
        "total_spawned": int(m.spawned[sel].sum()),
        "mean_speed": float(speed.mean()),
        "p10_speed": float(np.percentile(speed, 10)),
        "p90_speed": float(np.percentile(speed, 90)),
        "total_collisions": int(m.collisions[sel].sum()),
        "collision_rate_per_kstep": float(
            1000.0 * m.collisions[sel].sum() / max(total_steps, 1.0)
        ),
        "total_merges": int(m.merges_ok[sel].sum()),
        "total_ramp_blocked_steps": int(m.ramp_blocked_steps[sel].sum()),
        "min_ttc": float(m.min_ttc[sel].min()),
        "total_sim_steps": int(total_steps),
        "total_exits_missed": int(m.exits_missed[sel].sum()),
    }


def aggregate_metrics(
    metrics: SimMetrics,
    scenario_ids: Any = None,
    scenario_names: Sequence[str] | None = None,
) -> dict[str, Any]:
    """Population summary over a sweep — the 'massive output dataset' digest.

    With ``scenario_ids``/``scenario_names`` the summary also carries a
    ``per_scenario`` dict: the same digest grouped by workload (a mixed
    sweep's per-scenario completion/throughput/safety table).
    """
    m = jax.tree.map(lambda x: np.asarray(jax.device_get(x)), metrics)
    all_sel = np.ones(m.throughput.shape[0], bool)
    out: dict[str, Any] = _summarize(m, all_sel)
    if scenario_ids is not None and scenario_names is not None:
        ids = np.asarray(jax.device_get(scenario_ids))
        per: dict[str, Any] = {}
        # group by NAME, not roster slot: a weighted mix may list the same
        # scenario several times (e.g. stop_and_go,stop_and_go,highway_merge)
        for name in dict.fromkeys(scenario_names):  # unique, order-stable
            slots = [s for s, n in enumerate(scenario_names) if n == name]
            sel = np.isin(ids, slots)
            if not sel.any():
                continue
            sub = _summarize(m, sel)
            # rename the generic slots to what they mean for this workload
            for generic, alias in get_scenario(name).metric_aliases.items():
                total_key = {
                    "merges_ok": "total_merges",
                    "throughput": "total_throughput",
                    "spawned": "total_spawned",
                    "collisions": "total_collisions",
                    "ramp_blocked_steps": "total_ramp_blocked_steps",
                }.get(generic)
                if total_key and total_key in sub:
                    sub[f"total_{alias}"] = sub.pop(total_key)
            per[name] = sub
        out["per_scenario"] = per
    return out
