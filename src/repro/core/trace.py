"""Spans and counters of the sweep, on JAX's own channels.

A span is written twice, and read by whoever listens:

- as a ``jax.profiler.TraceAnnotation`` on the profiler's host plane, so a
  profile of a few chunks shows what the host did on the same clock as the
  device ops;
- as a ``jax.monitoring`` time span (``time.perf_counter`` seconds), so a
  listener registered with
  ``jax.monitoring.register_event_time_span_listener`` sees every span of
  a run without a profiler.

A counter is a ``jax.monitoring.record_scalar`` event. With no listener
and no profiler a span costs two clock reads and one annotation object.
Spans nest on the host thread: a span opened inside another is its child.
No span reads a device value or waits for the device; chunk ids in the
metadata come from the host's own counter.

The sweep's spans (``docs/ARCHITECTURE.md``, "Spans and counters"):

- ``sweep.chunk``: one ``SweepRunner.run_chunk``; inside it ``sweep.sync``
  (pulling the completion bitmap), ``sweep.plan`` (the host planner),
  and per group or block ``sweep.gather``, ``sweep.step`` (the program
  call), ``sweep.scatter``;
- ``fleet.sync`` (the supervisor's reads of ``done`` and the chunk
  counter), ``fleet.revert``, ``fleet.ckpt``, ``fleet.drain``,
  ``fleet.audit``, ``fleet.journal``, each with ``chunk=`` the chunk it
  serves;
- counter ``sweep.slot_steps``: slot-steps one group or block computes
  (rows including padding x chunk steps x slots), with ``impl=`` the
  neighbour engine they ran on (``auto`` resolved).

On the device, ``sim_step`` names its phases with ``jax.named_scope``
(``neighbors``, ``longitudinal``, ``lane_change``, ``spawn``) and the
chunk rollout names its recorder (``record``): a profile attributes each
device op to the phase in its ``op_name``.
"""

from __future__ import annotations

import time

import jax

# the device phases' scope names, as ``jax.named_scope`` writes them
NEIGHBORS, LONGITUDINAL, LANE_CHANGE, SPAWN, RECORD = PHASES = (
    "neighbors", "longitudinal", "lane_change", "spawn", "record")


class span:
    """``with span("sweep.gather"): ...`` -- a named host span.

    ``meta`` (str or int values) rides on both the annotation and the
    monitoring event.
    """

    __slots__ = ("name", "meta", "_mark", "_t0")

    def __init__(self, name: str, **meta: str | int) -> None:
        self.name = name
        self.meta = meta

    def __enter__(self) -> "span":
        self._mark = jax.profiler.TraceAnnotation(self.name, **self.meta)
        self._mark.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        t1 = time.perf_counter()
        self._mark.__exit__(*exc)
        jax.monitoring.record_event_time_span(self.name, self._t0, t1,
                                              **self.meta)


def count(name: str, value: int | float, **meta: str | int) -> None:
    """Record one counter reading (``jax.monitoring.record_scalar``)."""
    jax.monitoring.record_scalar(name, value, **meta)
