"""The comparison that decides ``correct``: the program's answers against
the plain reference (``reference.py``), number by number, each against
the limit the configuration file gives it.

The answers are what the timed path produced for a sample of instances
drawn from the run's seed: the final vehicle state, the counters, and every
recorded row (from the shards a sweep wrote, or from the trace buffer of a
sweep still running). The reference rebuilds the same instances from the
sweep seed and steps each to the step count the harness itself counted
(never the program's own clock), so a chunk that did not advance an
instance shows as ``steps_gap``. A scenario module of the reference may
add counters (``COUNTERS``) and per-vehicle fields compared exactly
(``EXACT``); they count as ``counter_gap`` and ``slot_mismatch``.
"""

from __future__ import annotations

import numpy as np

import reference

# the counters every scenario has, compared exactly (vehicle-steps, exits,
# arrivals, ...); reference name -> program SimMetrics field
COUNTERS = {
    "throughput": "throughput", "spawned": "spawned",
    "speed_count": "speed_count", "collisions": "collisions",
    "merges_ok": "merges_ok", "blocked": "ramp_blocked_steps",
    "lane_changes": "lane_changes", "steps": "steps",
}
NUMBERS = ("steps_gap", "slot_mismatch", "counter_gap", "pos_gap_m",
           "vel_gap_mps", "series_gap", "speed_sum_rel_gap")


def counter_names(scenario: str) -> dict:
    """Reference name -> SimMetrics field of every counter compared for an
    instance of ``scenario``: the shared ones and its module's own."""
    return COUNTERS | reference.scenario(scenario).counters


def sample(n: int, k: int, rng: np.random.Generator, eligible=None,
           longest=None) -> list[int]:
    """``k`` instance ids, one from each of ``k`` contiguous blocks of the
    ``n`` (so every device block and worker range is represented), drawn
    among ``eligible`` ones; ``longest`` (per-instance lengths) puts the
    longest eligible instance in the sample."""
    eligible = np.ones(n, bool) if eligible is None else np.asarray(eligible)
    picks = []
    for b in np.array_split(np.arange(n), min(k, n)):
        ok = b[eligible[b]]
        if ok.size:
            picks.append(int(rng.choice(ok)))
    if longest is not None:
        lengths = np.where(eligible, np.asarray(longest), -1)
        top = int(np.argmax(lengths))
        if top not in picks and picks:
            picks[int(np.argmin(np.abs(np.asarray(picks) - top)))] = top
    return sorted(set(picks))


def _gap(a, b) -> float:
    """Widest |a - b|; a NaN on one side only is an infinite gap."""
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    if a.size == 0:
        return 0.0
    d = np.abs(a - b)
    d = np.where(np.isnan(a) & np.isnan(b), 0.0, d)
    return float(np.nanmax(np.where(np.isnan(d), np.inf, d)))


def compare(answers: dict, expected: dict) -> dict:
    """The numbers compared, over every sampled instance. ``answers`` and
    ``expected`` map instance id -> {"t", "veh", "counters", "series",
    "lane", "speed", "active"} (the program's counters under SimMetrics
    names, the reference's under its own), and ``expected`` also to the
    instance's "scenario"."""
    out = dict.fromkeys(NUMBERS, 0.0)
    for i, ref in expected.items():
        got = answers[i]
        out["steps_gap"] = max(out["steps_gap"], abs(got["t"] - ref["t"]))
        gv, rv = got["veh"], ref["veh"]
        both = gv["active"] & rv["active"]
        out["slot_mismatch"] += int(np.sum(gv["active"] != rv["active"])
                                    + np.sum(both & (gv["lane"] != rv["lane"])))
        for f in reference.scenario(ref["scenario"]).exact:
            out["slot_mismatch"] += int(np.sum(both & (gv[f] != rv[f])))
        out["pos_gap_m"] = max(out["pos_gap_m"],
                               _gap(gv["pos"][both], rv["pos"][both]))
        out["vel_gap_mps"] = max(out["vel_gap_mps"],
                                 _gap(gv["vel"][both], rv["vel"][both]))
        gc, rc = got["counters"], ref["counters"]
        for r_name, p_name in counter_names(ref["scenario"]).items():
            out["counter_gap"] = max(out["counter_gap"],
                                     _gap(gc[p_name], rc[r_name]))
        s_ref = float(rc["speed_sum"])
        out["speed_sum_rel_gap"] = max(
            out["speed_sum_rel_gap"],
            _gap(gc["speed_sum"], s_ref) / max(abs(s_ref), 1.0))
        rows = min(len(got["series"]), len(ref["series"]))
        if len(got["series"]) != len(ref["series"]):
            out["slot_mismatch"] += abs(len(got["series"]) - len(ref["series"]))
        out["series_gap"] = max(
            out["series_gap"],
            _gap(got["series"][:rows], ref["series"][:rows]),
            _gap(gc["min_ttc"], rc["min_ttc"]))
        ga, ra = got["active"][:rows], ref["active"][:rows]
        kb = ga & ra
        out["slot_mismatch"] += int(np.sum(ga != ra) + np.sum(
            kb & (got["lane"][:rows] != ref["lane"][:rows])))
        out["series_gap"] = max(out["series_gap"], _gap(
            got["speed"][:rows][kb], ref["speed"][:rows][kb]))
    return out


def judge(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """``correct`` and each number beside its limit."""
    checks = {k: {"value": float(v), "limit": float(limits[k])}
              for k, v in numbers.items()}
    ok = all(np.isfinite(c["value"]) and c["value"] <= c["limit"]
             for c in checks.values())
    return ok, checks
