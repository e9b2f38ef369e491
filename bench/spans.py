"""What one run of the benchmark records: host spans around the calls it
makes into each layer of the program, compile events, chunk boundaries and
the slot-steps each chunk plan computes.

The spans are taken on the host clock (``time.perf_counter``) and, in a
traced run, also written as ``jax.profiler.TraceAnnotation`` events so the
profiler's trace shows what the host was doing in each device idle gap.
Nothing here edits the program: each wrapper replaces a method on one
object the benchmark built (a runner, a checkpoint manager, a dataset
writer, a journal).
"""

from __future__ import annotations

import dataclasses
import functools
import time

# what each wrapped call is, as the breakdown names it
LABELS = {
    "run_chunk": "chunk",
    "plan_chunk": "plan",
    "plan_chunk_sharded": "plan",
    "_host_bitmap": "bitmap_sync",
    "_run_group": "gather_scatter",
    "_run_block": "gather_scatter",
    "save": "ckpt",
    "drain": "drain",
    "begin_drain": "drain",
    "finish_drain": "drain",
    "verify_shards": "drain",
    "append": "journal",
}
DURABLE = ("ckpt", "drain", "journal")
HOST = ("chunk",)


@dataclasses.dataclass
class Record:
    """Everything a per-layer reader may look at (host clock, seconds)."""

    traced: bool = False
    spans: list = dataclasses.field(default_factory=list)     # (label, t0, t1)
    compiles: list = dataclasses.field(default_factory=list)  # (t_end, secs)
    cache_hits: list = dataclasses.field(default_factory=list)
    chunk_entries: list = dataclasses.field(default_factory=list)  # t
    plans: list = dataclasses.field(default_factory=list)     # (chunk, slot_steps)
    window: tuple = (0.0, 0.0)
    window_chunks: tuple = (0, 0)     # [first, last) chunk-entry indices
    veh_steps: float = 0.0
    slot_steps_per_row: int = 0       # chunk_steps * n_slots
    device: dict = dataclasses.field(default_factory=dict)    # trace reduction
    neighbor_build_s: float | None = None

    # ---- listeners -------------------------------------------------------

    def _duration(self, event: str, secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles.append((time.perf_counter(), secs))

    def _event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits.append(time.perf_counter())

    def listen(self) -> None:
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def unlisten(self) -> None:
        import jax

        jax.monitoring.unregister_event_duration_listener(self._duration)
        jax.monitoring.unregister_event_listener(self._event)

    # ---- wrappers --------------------------------------------------------

    def wrap(self, obj, name: str, after=None) -> None:
        """Replace ``obj.name`` by a call that records a span around it;
        ``after(result, args)`` sees what the call returned."""
        inner = getattr(obj, name)
        label = LABELS[name]
        traced = self.traced

        @functools.wraps(inner)
        def call(*args, **kwargs):
            t0 = time.perf_counter()
            if traced:
                import jax

                with jax.profiler.TraceAnnotation(label):
                    out = inner(*args, **kwargs)
            else:
                out = inner(*args, **kwargs)
            self.spans.append((label, t0, time.perf_counter()))
            if after is not None:
                after(out, args)
            return out

        setattr(obj, name, call)

    def wrap_durable(self, kw: dict) -> None:
        """Spans around the fleet supervisor's durable writes."""
        if kw.get("ckpt") is not None:
            self.wrap(kw["ckpt"], "save")
        if kw.get("writer") is not None:
            for name in ("drain", "begin_drain", "finish_drain",
                         "verify_shards"):
                self.wrap(kw["writer"], name)
        if kw.get("journal") is not None:
            self.wrap(kw["journal"], "append")

    def wrap_runner(self, runner, chunk_index) -> None:
        """Spans around the planner and executor; each plan's computed
        slot-steps are booked against ``chunk_index()``."""

        def book_groups(plans, _args):
            rows = sum(int(p.take.size) for p in plans)
            self.plans.append((chunk_index(), rows * self.slot_steps_per_row))

        def book_blocks(bp, _args):
            rows = 0 if bp is None else int(bp.take.size)
            self.plans.append((chunk_index(), rows * self.slot_steps_per_row))

        self.wrap(runner, "_host_bitmap")
        self.wrap(runner, "plan_chunk", after=book_groups)
        self.wrap(runner, "plan_chunk_sharded", after=book_blocks)
        self.wrap(runner, "_run_group")
        self.wrap(runner, "_run_block")

    # ---- reductions the readers share -------------------------------------

    def in_window(self, t: float) -> bool:
        return self.window[0] <= t <= self.window[1]

    def chunks_in_window(self) -> int:
        first, last = self.window_chunks
        return last - first

    def union_s(self, labels, lo=None, hi=None) -> float:
        """Seconds covered by spans of ``labels`` inside [lo, hi] (the
        window by default); nested or overlapping spans count once."""
        lo = self.window[0] if lo is None else lo
        hi = self.window[1] if hi is None else hi
        cuts = sorted((max(a, lo), min(b, hi)) for lab, a, b in self.spans
                      if lab in labels and b > lo and a < hi)
        total, end = 0.0, lo
        for a, b in cuts:
            if b <= end:
                continue
            total += b - max(a, end)
            end = b
        return total
