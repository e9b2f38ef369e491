#!/usr/bin/env python3
"""Readings of the program's own spans, counters and device scopes, from
one traced run of a cell.

    python3 bench/phases.py --workload <name> --seed <n> --seconds <s>

The program names its work (``repro.core.trace``): host spans ``sweep.*``
and ``fleet.*`` on ``jax.monitoring`` and on the profiler's host plane,
the counter ``sweep.slot_steps``, and ``jax.named_scope`` phases on the
device ops of ``sim_step`` and the chunk rollout. This probe runs the cell
once through ``run.run_cell`` with ``--trace 1``, listens to those
channels, keeps the profiler's trace before the harness removes it, and
prints one JSON line with:

- ``phases``: the per-layer readings below, under their names;
- ``profiled``: the host readings over the chunks inside the profiled
  part, beside ``phases``' readings over the chunks after it;
- ``busy_ms_per_chunk``: chip 0's busy union per chunk, which the five
  phases plus ``unscoped`` must add up to;
- ``idle_gaps``: the traced window's idle time on chip 0 by the innermost
  covering span, harness and program spans together
  (``tracefile.reduce``), beside ``idle_gaps_harness`` (harness spans only);
- ``resolved``: the share of device op time whose HLO instruction was
  found in the HLO protos the trace keeps (each op's ``op_name`` comes
  from there: the ``XLA Ops`` events carry no such stat on a v5e);
- ``compiles``: each backend compile after the window opened, by name;
- ``result``: the harness's own result object.

Device readings are per chunk entry in the traced window (program
``sweep.chunk`` spans that start in it), averaged over the chips that ran
ops. Host readings (``exec_host_ms_per_chunk``, ``sync_wait_ms_per_chunk``)
count only chunks that start after the profiler stopped, so the profiler's
Python tracer is not in them; ``None`` when no whole chunk is left.
``--tiny`` runs the CPU rehearsal's size on virtual CPU devices (paths and
control flow only: no number it prints is a device metric).
"""

from __future__ import annotations

import argparse
import bisect
import glob
import json
import os
import re
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))

import tracefile  # noqa: E402
from repro.core.trace import PHASES  # noqa: E402

UNSCOPED = "unscoped"
EXECUTOR = ("sweep.plan", "sweep.gather", "sweep.step", "sweep.scatter")
SYNC = ("sweep.sync", "fleet.sync")
PROGRAM = ("sweep.", "fleet.")
MODULES_LINE = "XLA Modules"
_OP_NAME = re.compile(r'op_name="([^"]*)"')


# ---- reading the trace ------------------------------------------------------

def scope_of(op_name: str) -> str:
    """The innermost phase scope named in an op's ``op_name`` path
    (``jit(f)/while/body/lane_change/neighbors/gather`` -> ``neighbors``),
    else ``unscoped``. A transform may wrap a scope: ``jvp(spawn)``."""
    found = UNSCOPED
    for part in op_name.split("/")[:-1]:
        inner = part.rstrip(")").rsplit("(", 1)[-1]
        if inner in PHASES:
            found = inner
    return found


def _varint(b, i: int) -> tuple[int, int]:
    x = shift = 0
    while True:
        c = b[i]
        i += 1
        x |= (c & 0x7F) << shift
        shift += 7
        if c < 0x80:
            return x, i


def _fields(b):
    """(field number, value) of one protobuf message, in order: ints for
    varints, memoryview slices for length-delimited and fixed fields."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(b, i)
        elif wire == 2:
            size, i = _varint(b, i)
            value, i = b[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = b[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield field, value


def _first(b, field):
    return next((v for f, v in _fields(b) if f == field), None)


def _text(b) -> str:
    return bytes(b).decode("utf-8", "replace") if b is not None else ""


def hlo_op_names(xplane: bytes) -> dict[int, tuple[str, dict[str, str]]]:
    """program id -> (module name, {HLO instruction -> ``op_name``}), from
    the HLO protos the profiler keeps on the trace's ``/host:metadata``
    plane (``ProfileOptions.enable_hlo_proto``, on by default).

    Read from the raw ``XSpace`` (planes 1; plane name 2, event metadata 4
    as map entries of key 1 and ``XEventMetadata`` 2, whose stats 5 carry
    the ``HloProto`` as ``bytes_value`` 6; ``HloProto.hlo_module`` 1,
    module name 1, computations 3, instructions 2, instruction name 1,
    ``OpMetadata`` 7, ``op_name`` 2)."""
    out: dict = {}
    for f, plane in _fields(memoryview(xplane)):
        if f != 1 or _text(_first(plane, 2)) != "/host:metadata":
            continue
        for f2, entry in _fields(plane):
            if f2 != 4:
                continue
            pid, meta = _first(entry, 1), _first(entry, 2)
            for f3, stat in _fields(meta if meta is not None else b""):
                proto = _first(stat, 6) if f3 == 5 else None
                module = _first(proto, 1) if proto is not None else None
                if module is None:
                    continue
                names = {}
                for f4, comp in _fields(module):
                    if f4 != 3:
                        continue
                    for f5, ins in _fields(comp):
                        md = _first(ins, 7) if f5 == 2 else None
                        op = _first(md, 2) if md is not None else None
                        if op is not None and len(op):
                            names[_text(_first(ins, 1))] = _text(op)
                out[int(pid or 0)] = (_text(_first(module, 1)), names)
    return out


_PROGRAM_ID = re.compile(r"\((\d+)\)\s*$")


def instruction(event_name: str) -> str:
    """The HLO instruction an ``XLA Ops`` event names: its name may be the
    instruction's text (``%fusion.6 = f32[8]{0} fusion(...)``)."""
    return event_name.lstrip("%").split(" ", 1)[0]


class OpScopes:
    """Phase scope of a device op from its program's HLO: by program id
    (an ``XLA Modules`` event ``jit_f(12)``), else by module name, else by
    the instruction name in any program."""

    def __init__(self, hlo: dict) -> None:
        self.by_id = {pid: names for pid, (_, names) in hlo.items()}
        self.by_module: dict[str, dict] = {}
        self.anywhere: dict[str, str] = {}
        for module, names in hlo.values():
            self.by_module.setdefault(module, {}).update(names)
            self.anywhere.update(names)
        self._cache: dict = {}

    def scope(self, module_event: str | None, op_event: str) -> str | None:
        """The op's scope, or None where no HLO names the instruction."""
        key = (module_event, op_event)
        if key not in self._cache:
            m = _OP_NAME.search(op_event)
            path = m.group(1) if m else None
            if path is None:
                inst = instruction(op_event)
                pid = (_PROGRAM_ID.search(module_event)
                       if module_event else None)
                name = (module_event or "").split("(", 1)[0]
                for table in (self.by_id.get(int(pid.group(1))) if pid
                              else None,
                              self.by_module.get(name), self.anywhere):
                    if table and inst in table:
                        path = table[inst]
                        break
            self._cache[key] = None if path is None else scope_of(path)
        return self._cache[key]


def _module_at(modules, starts, t: float) -> str | None:
    """The ``XLA Modules`` event running at ``t`` on the same chip."""
    k = bisect.bisect_right(starts, t) - 1
    if k >= 0 and t <= modules[k][1]:
        return modules[k][2]
    return None


def extract(trace_dir: str) -> dict:
    """``tracefile.extract`` (the window, each TPU plane's ops), plus, per
    TPU plane, each op's phase scope (``ops``: [[scope, start_ns,
    dur_ns]]) and the start of each program launch (``modules``), and the
    host plane's named spans (``spans``: [[name, start_ns, end_ns]]: the
    program's and the harness's). ``resolved`` is the share of device op
    time whose HLO instruction was found (its op then has a scope or is
    ``unscoped``)."""
    from jax.profiler import ProfileData

    import spans as harness

    out = tracefile.extract(trace_dir)
    labels = set(harness.LABELS.values())
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    with open(max(paths, key=os.path.getmtime), "rb") as f:
        raw = f.read()
    scopes = OpScopes(hlo_op_names(raw))
    data = ProfileData.from_serialized_xspace(raw)
    del raw
    out.update(ops={}, modules={}, spans=[], hlo_programs=len(scopes.by_id))
    found = total = 0.0
    for plane in data.planes:
        if plane.name in out["devices"]:
            modules = sorted(
                (float(e.start_ns), float(e.start_ns + e.duration_ns), e.name)
                for line in plane.lines if line.name == MODULES_LINE
                for e in line.events)
            starts = [m[0] for m in modules]
            scoped = []
            for name, s, d in out["devices"][plane.name]:
                scope = scopes.scope(_module_at(modules, starts, s), name)
                total += d
                found += d if scope is not None else 0.0
                scoped.append([scope or UNSCOPED, s, d])
            out["ops"][plane.name] = scoped
            out["modules"][plane.name] = starts
        elif plane.name.startswith("/host:"):
            out["spans"].extend(
                [e.name, float(e.start_ns), float(e.start_ns + e.duration_ns)]
                for line in plane.lines for e in line.events
                if e.name.startswith(PROGRAM) or e.name in labels)
    out["resolved"] = found / total if total else None
    return out


# ---- reductions -------------------------------------------------------------

def self_time_by_scope(ops, lo: float, hi: float) -> dict[str, float]:
    """Self time (ns) of one line's ops by scope, clipped to [lo, hi]: each
    op's clipped duration less what the ops nested inside it cover. Ops on
    one line nest (a ``while`` holds its body's ops), so the scopes add up
    to the line's busy union."""
    clipped = sorted(((max(s, lo), min(s + d, hi), scope)
                      for scope, s, d in ops if s + d > lo and s < hi),
                     key=lambda o: (o[0], -o[1]))
    out: dict[str, float] = {}
    stack: list[list] = []      # [start, end, scope, self, covered_until]

    def close(op):
        start, end, scope, own, _ = op
        out[scope] = out.get(scope, 0.0) + own
        if stack:               # its parent loses what this op covered
            parent = stack[-1]
            parent[3] -= max(0.0, end - max(start, parent[4]))
            parent[4] = max(parent[4], end)

    for a, b, scope in clipped:
        while stack and stack[-1][1] <= a:
            close(stack.pop())
        if stack:               # an op never reaches past its parent's end
            b = min(b, stack[-1][1])
        stack.append([a, b, scope, b - a, a])
    while stack:
        close(stack.pop())
    return out


def chunks_in(spans, lo: float, hi: float) -> int:
    """Chunk entries (``sweep.chunk`` span starts) in [lo, hi)."""
    return sum(1 for name, a, _ in spans
               if name == "sweep.chunk" and lo <= a < hi)


def device_readings(ex: dict) -> dict:
    """The device readings of the traced window: ms per chunk of each phase
    and of ``unscoped``, programs launched on chip 0 per chunk, and chip
    0's busy union per chunk (the check of the phase sum)."""
    planes = {k: v for k, v in ex["ops"].items() if v}
    if not planes or ex["window"] is None:
        return {}
    lo, hi = ex["window"]
    n = chunks_in(ex["spans"], lo, hi)
    if not n:
        return {}
    total: dict[str, float] = {}
    for ops in planes.values():
        for scope, ns in self_time_by_scope(ops, lo, hi).items():
            total[scope] = total.get(scope, 0.0) + ns
    per = 1e-6 / (n * len(planes))                  # ns -> ms per chunk, chip
    first = min(planes)
    chip0 = self_time_by_scope(planes[first], lo, hi)
    out = {f"{p}_ms_per_chunk": total.get(p, 0.0) * per
           for p in PHASES + (UNSCOPED,)}
    out["programs_per_chunk"] = sum(
        1 for t in ex["modules"].get(first, ()) if lo <= t < hi) / n
    out["busy_ms_per_chunk"] = tracefile.busy_ns(
        ex["devices"][first], lo, hi) * 1e-6 / n
    out["chip0_scopes_ms_per_chunk"] = sum(chip0.values()) * 1e-6 / n
    out["chunks"] = n
    return out


def self_seconds(spans, names) -> float:
    """Self time (s) of the spans named ``names``: each one's duration less
    what the spans nested inside it (of any name) cover."""
    total = 0.0
    for name, a, b in spans:
        if name not in names:
            continue
        inner = [(max(x, a), min(y, b)) for n2, x, y in spans
                 if (x, y, n2) != (a, b, name) and a <= x and y <= b]
        covered = sum(q - p for p, q in tracefile._union(inner, a, b))
        total += (b - a) - covered
    return total


def host_readings(spans, lo: float, hi: float) -> dict:
    """Host ms per chunk over the chunks that start in [lo, hi): executor
    self time (``sweep.plan``/``gather``/``step``/``scatter``, syncs
    excluded) and sync waits (``sweep.sync`` + ``fleet.sync``). Spans are
    (name, t0_s, t1_s) on one clock."""
    part = [s for s in spans if lo <= s[1] < hi]
    n = chunks_in(part, lo, hi)
    if not n:
        return {"exec_host_ms_per_chunk": None,
                "sync_wait_ms_per_chunk": None, "chunks": 0}
    return {
        "exec_host_ms_per_chunk": 1e3 * self_seconds(part, EXECUTOR) / n,
        "sync_wait_ms_per_chunk":
            1e3 * sum(b - a for name, a, b in part if name in SYNC) / n,
        "chunks": n,
    }


def idle_gaps(ex: dict, top: int = 100) -> tuple[list, list]:
    """(idle gaps by the innermost harness or program span, idle gaps by
    harness spans alone): ``tracefile.reduce`` on the same trace, the host
    spans handed over on the trace's own clock."""
    if ex["window"] is None:
        return [], []
    lo = ex["window"][0]
    rel = [(name, (a - lo) / 1e9, (b - lo) / 1e9)
           for name, a, b in ex["spans"]]
    harness = [s for s in rel if not s[0].startswith(PROGRAM)]
    both = tracefile.reduce(ex, rel, 0.0, top=top) or {}
    alone = tracefile.reduce(ex, harness, 0.0, top=top) or {}
    return both.get("idle_gaps", []), alone.get("idle_gaps", [])


# ---- the probe --------------------------------------------------------------

class Listener:
    """The program's spans and counters, and every backend compile with the
    program it built, as ``jax.monitoring`` delivers them."""

    def __init__(self) -> None:
        self.spans: list = []       # (name, t0, t1) perf_counter seconds
        self.scalars: list = []     # (name, value, t)
        self.compiles: list = []    # (t_end, seconds, fun_name)

    def _span(self, name, t0, t1, **_meta):
        if name.startswith(PROGRAM):
            self.spans.append((name, t0, t1))

    def _scalar(self, name, value, **_meta):
        if name.startswith(PROGRAM):
            self.scalars.append((name, value, time.perf_counter()))

    def _duration(self, name, secs, **meta):
        if name == "/jax/core/compile/backend_compile_duration":
            self.compiles.append((time.perf_counter(), secs,
                                  str(meta.get("fun_name", "?"))))

    def __enter__(self):
        import jax

        jax.monitoring.register_event_time_span_listener(self._span)
        jax.monitoring.register_scalar_listener(self._scalar)
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        return self

    def __exit__(self, *exc):
        import jax

        jax.monitoring.unregister_event_time_span_listener(self._span)
        jax.monitoring.unregister_scalar_listener(self._scalar)
        jax.monitoring.unregister_event_duration_listener(self._duration)


def probe(workload: str, seed: int, seconds: float, tiny: bool = False,
          out_dir: str | None = None) -> dict:
    import run

    kept: dict = {}

    class KeepTrace(run.Tracer):
        """The harness's tracer, reading the trace as soon as it is
        written (the harness removes it when the run ends)."""

        def stop(self) -> None:
            was_on = self._mark is not None
            super().stop()
            if was_on:
                t = time.perf_counter()
                kept["ex"] = extract(self.dir)
                kept["t0"], kept["t1"] = self.t0, self.t1
                kept["extract_s"] = time.perf_counter() - t

    over = None
    if tiny:
        import rehearse

        over = rehearse.tiny(workload)
    real = run.Tracer
    run.Tracer = KeepTrace
    try:
        with Listener() as lis:
            result = run.run_cell(workload, seed, seconds, True,
                                  allow_cpu=tiny, overrides=over)
    finally:
        run.Tracer = real
    if "ex" not in kept:
        raise RuntimeError("the run wrote no trace")
    ex, t0, t1 = kept["ex"], kept["t0"], kept["t1"]
    end = max(b for _, _, b in lis.spans)
    phases = device_readings(ex)
    after = host_readings(lis.spans, t1, end)
    inside = host_readings(lis.spans, t0, t1)
    both, alone = idle_gaps(ex)
    reading = {k: phases.get(k) for k in
               [f"{p}_ms_per_chunk" for p in PHASES + (UNSCOPED,)]
               + ["programs_per_chunk"]}
    reading.update({k: after[k] for k in ("exec_host_ms_per_chunk",
                                          "sync_wait_ms_per_chunk")})
    entries = sorted(a for name, a, _ in lis.spans if name == "sweep.chunk")
    periods = [b - a for a, b in zip(entries, entries[1:])]
    compiles = [[round(t - t0, 3), s, name] for t, s, name in lis.compiles
                if t0 <= t <= end]
    for c in compiles:
        print(f"[phases] compile in the window at +{c[0]}s: {c[2]} "
              f"({c[1]:.3f}s)", file=sys.stderr)
    out = {
        "workload": workload, "seed": seed, "device": result["device"],
        "correct": result["correct"], "phases": reading,
        "profiled": {k: inside[k] for k in ("exec_host_ms_per_chunk",
                                            "sync_wait_ms_per_chunk",
                                            "chunks")},
        "after_profile_chunks": after["chunks"],
        "busy_ms_per_chunk": phases.get("busy_ms_per_chunk"),
        "chip0_scopes_ms_per_chunk": phases.get("chip0_scopes_ms_per_chunk"),
        "traced_chunks": phases.get("chunks"),
        "chunk_period_s": {
            "profiled": _median([b - a for a, b in zip(entries, entries[1:])
                                 if t0 <= a and b <= t1]),
            "after": _median([b - a for a, b in zip(entries, entries[1:])
                              if a >= t1]),
            "all": _median(periods)},
        "slot_steps_after_profile": sum(v for name, v, t in lis.scalars
                                        if name == "sweep.slot_steps"
                                        and t >= t1),
        "idle_gaps": both, "idle_gaps_harness": alone,
        "resolved": ex["resolved"], "hlo_programs": ex["hlo_programs"],
        "scopes_seen": sorted({s for ops in ex["ops"].values()
                               for s, _, _ in ops}),
        "extract_s": kept["extract_s"], "compiles": compiles,
        "result": result,
    }
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        path = os.path.join(out_dir, f"phases_{workload}_{seed}.json")
        with open(path, "w") as f:
            json.dump(out, f, indent=1)
    return out


def _median(xs):
    xs = sorted(xs)
    return xs[len(xs) // 2] if xs else None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--out", default=None,
                    help="directory for the whole reading as a JSON file")
    args = ap.parse_args(argv)
    if args.tiny:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                                   " --xla_force_host_platform_device_count=4")
    out = probe(args.workload, args.seed, args.seconds, args.tiny, args.out)
    brief = {k: v for k, v in out.items() if k != "result"}
    print(json.dumps(brief), flush=True)
    return 0 if out["correct"] else 1


if __name__ == "__main__":
    sys.exit(main())
