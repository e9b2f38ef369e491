"""Reduction of a JAX profiler trace to the benchmark's device numbers.

``extract`` reads the ``.xplane.pb`` a traced run wrote and keeps what the
reduction needs: the device operations of every TPU plane (``XLA Ops``
line) and the window marker the harness writes on the host. ``reduce``
then computes, on the trace's own clock:

- ``busy_s``: the union of device-op intervals inside the window, per chip,
  averaged over the chips used;
- ``window_s``: the window's length;
- ``device_ops``: total device time by operation name, largest first;
- ``idle_gaps``: the window's idle time on chip 0, by the host span that
  covers each gap (``other`` where none does), largest first.

Host spans come from the harness's own record, shifted onto the trace
clock by the window marker, whose start both clocks saw.
"""

from __future__ import annotations

import glob
import os
import re

WINDOW = "bench_window"
_DEVICE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"


def extract(trace_dir: str) -> dict:
    """The compact trace: {"window": [t0_ns, t1_ns] or None,
    "devices": {plane: [[name, start_ns, dur_ns], ...]}}."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(max(paths, key=os.path.getmtime))
    out: dict = {"window": None, "devices": {}, "lines": {}}
    for plane in data.planes:
        out["lines"][plane.name] = {line.name: len(list(line.events))
                                    for line in plane.lines}
        if _DEVICE.match(plane.name):
            ops = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.extend([e.name, float(e.start_ns), float(e.duration_ns)]
                               for e in line.events)
            out["devices"][plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == WINDOW:
                        out["window"] = [float(e.start_ns),
                                         float(e.start_ns + e.duration_ns)]
    return out


def _union(intervals, lo, hi):
    """Merged [a, b] intervals clipped to [lo, hi], sorted."""
    merged: list[list[float]] = []
    for a, b in sorted((max(a, lo), min(b, hi)) for a, b in intervals
                       if b > lo and a < hi):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged


def busy_ns(ops, lo, hi) -> float:
    return sum(b - a for a, b in _union(((s, s + d) for _, s, d in ops),
                                        lo, hi))


def reduce(ex: dict, host_spans=(), perf_at_window: float | None = None,
           top: int = 10) -> dict | None:
    """Device numbers of the traced window, or None where the trace holds
    no device operation (nothing to read). ``host_spans`` are
    (label, t0_s, t1_s) on the host clock; ``perf_at_window`` is the host
    clock reading at the window marker's start."""
    devices = {k: v for k, v in ex["devices"].items() if v}
    if not devices or ex["window"] is None:
        return None
    lo, hi = ex["window"]
    busy = [busy_ns(ops, lo, hi) for ops in devices.values()]
    if not any(busy):
        return None
    totals: dict[str, float] = {}
    for ops in devices.values():
        for name, s, d in ops:
            if s + d > lo and s < hi:
                totals[name] = totals.get(name, 0.0) + d
    first = devices[min(devices)]
    gaps = []
    end = lo
    for a, b in _union(((s, s + d) for _, s, d in first), lo, hi) + [[hi, hi]]:
        if a > end:
            gaps.append((end, a))
        end = max(end, b)
    shift = None if perf_at_window is None else lo - perf_at_window * 1e9
    spans = [] if shift is None else [
        (lab, t0 * 1e9 + shift, t1 * 1e9 + shift) for lab, t0, t1 in host_spans
        if t1 * 1e9 + shift > lo and t0 * 1e9 + shift < hi]
    by_label: dict[str, float] = {}
    for a, b in gaps:
        # each piece of a gap goes to the innermost host span covering it
        near = [s for s in spans if s[2] > a and s[1] < b]
        cuts = sorted({a, b, *(t for s in near for t in s[1:] if a < t < b)})
        for p, q in zip(cuts, cuts[1:]):
            mid = (p + q) / 2
            inside = [s for s in near if s[1] <= mid <= s[2]]
            label = (min(inside, key=lambda s: s[2] - s[1])[0] if inside
                     else "other")
            by_label[label] = by_label.get(label, 0.0) + (q - p) / 1e9
    rank = lambda d: sorted(d.items(), key=lambda kv: -kv[1])[:top]  # noqa: E731
    return {
        "busy_s": sum(busy) / len(busy) / 1e9,
        "window_s": (hi - lo) / 1e9,
        "device_ops": [[k, v / 1e9] for k, v in rank(totals)],
        "idle_gaps": [[k, v] for k, v in rank(by_label)],
    }
