"""Output-dataset schema tests: golden record/summary keys + vectorized
records parity.

The Phase-III dataset is consumed downstream (shards, jsonl records, ML
feature code), so its *schema* is a contract: any drift in record keys,
per-scenario alias names or summary keys must fail loudly against the
committed fixture (tests/fixtures/aggregate_schema.json). Regenerate with

    PYTHONPATH=src:tests python tests/test_aggregate.py --regen
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.aggregate import (
    aggregate_metrics,
    metrics_to_columns,
    metrics_to_records,
)
from repro.core.scenario import ScenarioParams
from repro.core.simulator import SimMetrics

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "aggregate_schema.json")
ROSTER = ("highway_merge", "lane_drop", "stop_and_go", "speed_limit_zone",
          "us101_weave")
N = 8


def _synthetic_dataset():
    """Stacked [N] metrics/params without running a sweep — schema only
    depends on structure, and this keeps the golden test near-instant."""
    rng = np.random.default_rng(0)

    def stack(leaf):
        return jnp.asarray(
            np.arange(N, dtype=np.asarray(leaf).dtype) + np.asarray(leaf)
        )

    metrics = jax.tree.map(stack, SimMetrics.zeros())
    params = ScenarioParams(
        lambda_main=jnp.asarray(rng.random((N, 3), np.float32)),
        lambda_ramp=jnp.asarray(rng.random(N).astype(np.float32)),
        p_cav=jnp.asarray(rng.random(N).astype(np.float32)),
        v0_mean=jnp.asarray(30.0 + rng.random(N).astype(np.float32)),
        v0_ramp=jnp.asarray(rng.random(N).astype(np.float32)),
        seed=jnp.arange(N, dtype=jnp.uint32),
        aux0=jnp.asarray(rng.random(N).astype(np.float32)),
        aux1=jnp.asarray(rng.random(N).astype(np.float32)),
    )
    scenario_ids = np.arange(N) % len(ROSTER)
    return metrics, params, scenario_ids


def _current_schema() -> dict:
    metrics, params, sids = _synthetic_dataset()
    records = metrics_to_records(metrics, params, scenario_ids=sids,
                                 scenario_names=ROSTER)
    summary = aggregate_metrics(metrics, scenario_ids=sids,
                                scenario_names=ROSTER)
    per_scenario_record_keys = {}
    for rec in records:
        per_scenario_record_keys.setdefault(rec["scenario"], list(rec))
    return {
        "record_keys": {k: sorted(v)
                        for k, v in per_scenario_record_keys.items()},
        "summary_keys": sorted(summary),
        "per_scenario_summary_keys": {
            name: sorted(sub) for name, sub in summary["per_scenario"].items()
        },
    }


def test_output_dataset_schema_matches_golden_fixture():
    """Record keys (incl. per-scenario metric_aliases renames) and summary
    keys exactly match the committed fixture — schema drift fails loudly."""
    with open(FIXTURE) as f:
        golden = json.load(f)
    assert _current_schema() == golden, (
        "output-dataset schema drifted from tests/fixtures/"
        "aggregate_schema.json — if intentional, regenerate the fixture "
        "(see module docstring) and call out the schema change in the PR"
    )


def test_records_match_reference_implementation():
    """The vectorized metrics_to_records equals a straightforward
    per-instance reference on values and key ORDER (json round-trip
    stability), not just key sets."""
    metrics, params, sids = _synthetic_dataset()
    records = metrics_to_records(metrics, params, scenario_ids=sids,
                                 scenario_names=ROSTER)
    m = jax.tree.map(lambda x: np.asarray(x), metrics)
    p = jax.tree.map(lambda x: np.asarray(x), params)
    from repro.core.scenarios import get_scenario

    assert len(records) == N
    for i, rec in enumerate(records):
        assert rec["instance"] == i
        assert rec["throughput"] == int(m.throughput[i])
        assert rec["mean_speed"] == float(
            np.float64(m.speed_sum[i]) / max(float(m.speed_count[i]), 1.0)
        )
        assert rec["min_ttc"] == float(np.float64(m.min_ttc[i]))
        assert rec["lambda_main"] == [float(x) for x in p.lambda_main[i]]
        assert rec["p_cav"] == float(np.float64(p.p_cav[i]))
        name = ROSTER[sids[i]]
        assert rec["scenario"] == name
        for generic, alias in get_scenario(name).metric_aliases.items():
            assert rec[alias] == rec[generic]
        assert isinstance(rec["throughput"], int)
        assert isinstance(rec["mean_speed"], float)
    # key order is stable across instances of the same scenario
    for rec in records[len(ROSTER):]:
        ref = next(r for r in records if r["scenario"] == rec["scenario"])
        assert list(rec) == list(ref)


def test_metrics_to_columns_layout():
    metrics, params, sids = _synthetic_dataset()
    cols = metrics_to_columns(metrics, params, scenario_ids=sids,
                              scenario_names=ROSTER)
    for k, v in cols.items():
        assert v.shape[0] == N, k
    assert cols["lambda_main"].shape == (N, 3)
    assert cols["throughput"].dtype == np.int64
    assert cols["scenario"][1] == "lane_drop"
    # scalar param leaves broadcast to per-instance columns
    params2 = params._replace(aux0=jnp.zeros(()))
    cols2 = metrics_to_columns(metrics, params2)
    assert cols2["aux0"].shape == (N,)


if __name__ == "__main__":
    import sys

    if "--regen" in sys.argv:
        os.makedirs(os.path.dirname(FIXTURE), exist_ok=True)
        with open(FIXTURE, "w") as f:
            json.dump(_current_schema(), f, indent=1, sort_keys=True)
            f.write("\n")
        print(f"regenerated {FIXTURE}")
