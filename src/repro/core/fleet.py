"""Unattended-run fleet supervision: retry budgets, quarantine, journaling.

The paper's operational headline is a 100 % simulation completion rate
over 12-hour unattended runs (§5.2) — PBS re-queues whatever dies. This
module is the in-process half of that contract (the process half is
``repro.launch.controller``): a supervised run loop that survives the
full fault taxonomy of :class:`repro.core.fault.FaultModel` without a
human in the loop, and degrades gracefully instead of thrashing:

- **Retry budgets.** Every reverted instance is charged a retry;
  re-queueing backs off exponentially (:class:`RetryPolicy`, in chunk
  units via the planner's ``hold`` mask) so a flapping worker doesn't
  burn its budget in consecutive chunks.
- **Quarantine.** An instance that exhausts its budget is quarantined —
  permanently held, excluded from scheduling and from the *eligible*
  completion denominator. One poison instance degrades only itself; the
  rest of the fleet still reaches 100 % (the ``run_with_failures`` loop
  this supersedes would re-queue it forever).
- **Run journal.** Every event (chunk committed, failure, quarantine,
  shard repair, deadline overrun) is appended to a crash-safe jsonl log
  whose failure events carry the *post-update* retry counters and hold
  horizons — so a resumed supervisor rebuilds its fleet state by plain
  replay-as-assignment (:meth:`FleetState.replay`), no reconciliation.
- **Durable-state audit.** Each chunk's checkpoint save and shard drain
  are followed by integrity hooks: injected corruption
  (``FaultModel.corrupt_ckpt`` / ``corrupt_shard``) truncates the newest
  artifact on disk, and recovery is exercised live — checkpoint restore
  falls back past digest-mismatched steps, the dataset writer's
  :meth:`~repro.data.shards.DatasetWriter.verify_shards` detects and
  rewrites the damage.

:func:`completion_report` reproduces the paper's §5.2 completion-rate
accounting per scenario, with quarantine called out explicitly;
:func:`format_completion_table` renders it as the README table.
"""

from __future__ import annotations

import dataclasses
import json
import os
import time
from typing import Any, Callable

import jax
import numpy as np

from repro.ckpt import CheckpointManager
from repro.ckpt.io import PAYLOAD, list_steps
from repro.core.fault import FailureInjector, FaultModel, revert_instances
from repro.core.sweep import SweepRunner, SweepState
from repro.core.trace import span


@dataclasses.dataclass(frozen=True)
class RetryPolicy:
    """Per-instance retry budget and exponential re-queue backoff.

    ``max_retries`` is the budget: an instance whose failure count
    *exceeds* it is quarantined (so the default 3 allows three reverts
    and quarantines on the fourth). After failure number ``k`` the
    instance is held out of scheduling for ``backoff_chunks(k)`` chunks —
    ``backoff_base * backoff_factor**(k-1)``, capped at ``backoff_cap``
    so a long sweep never idles an instance indefinitely.
    """

    max_retries: int = 3
    backoff_base: int = 1
    backoff_factor: float = 2.0
    backoff_cap: int = 8

    def backoff_chunks(self, n_failures: int) -> int:
        """Hold duration (in chunks) after the ``n_failures``-th failure."""
        raw = self.backoff_base * self.backoff_factor ** max(n_failures - 1, 0)
        return int(min(self.backoff_cap, raw))


class RunJournal:
    """Append-only jsonl event log — the run's crash-safe flight recorder.

    Each :meth:`append` writes one JSON line and fsyncs, so the journal
    survives a SIGKILL mid-run with at most a torn final line (which
    :meth:`read` skips). Events that mutate fleet state ("failure",
    "quarantine") carry the post-update values, making replay plain
    assignment — see :meth:`FleetState.replay`.
    """

    def __init__(self, path: str) -> None:
        self.path = path
        parent = os.path.dirname(path)
        if parent:
            os.makedirs(parent, exist_ok=True)

    def exists(self) -> bool:
        """True iff the journal file is present on disk."""
        return os.path.exists(self.path)

    def append(self, event: dict) -> None:
        """Durably append one event (adds a wall-clock ``time`` field)."""
        event = dict(event, time=time.time())
        with open(self.path, "a") as f:
            f.write(json.dumps(event) + "\n")
            f.flush()
            os.fsync(f.fileno())

    @staticmethod
    def read(path: str) -> list[dict]:
        """All parseable events, in append order. A torn line (kill
        mid-append) is skipped rather than poisoning the replay."""
        if not os.path.exists(path):
            return []
        events = []
        with open(path) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    events.append(json.loads(line))
                except json.JSONDecodeError:
                    continue
        return events


@dataclasses.dataclass
class FleetState:
    """Mutable per-instance supervision state (host-side, numpy).

    ``retries[i]`` counts charged failures, ``quarantined[i]`` marks a
    poison instance permanently removed from scheduling, and
    ``hold_until[i]`` is the first chunk index at which instance ``i``
    may run again (exponential backoff). Everything here is rebuilt from
    the journal on resume — it is deliberately NOT part of the jax
    checkpoint, so fleet bookkeeping never perturbs trajectory parity
    with an unsupervised run.
    """

    retries: np.ndarray      # [N] int64 — failures charged so far
    quarantined: np.ndarray  # [N] bool
    hold_until: np.ndarray   # [N] int64 — held while chunk < hold_until

    @staticmethod
    def zeros(n: int) -> "FleetState":
        """Fresh fleet state for ``n`` instances (no failures yet)."""
        return FleetState(
            retries=np.zeros(n, np.int64),
            quarantined=np.zeros(n, bool),
            hold_until=np.zeros(n, np.int64),
        )

    @staticmethod
    def replay(events: list[dict], n: int) -> "FleetState":
        """Rebuild fleet state from journal events by assignment.

        "failure" events carry post-update ``retries`` / ``hold_until``
        maps and "quarantine" events carry instance lists, so replay in
        append order converges to the exact state at the last fsync —
        the crash-safety contract of :class:`RunJournal`.
        """
        fs = FleetState.zeros(n)
        for e in events:
            kind = e.get("kind")
            if kind == "failure":
                for k, v in (e.get("retries") or {}).items():
                    fs.retries[int(k)] = int(v)
                for k, v in (e.get("hold_until") or {}).items():
                    fs.hold_until[int(k)] = int(v)
            elif kind == "quarantine":
                for i in e.get("instances", []):
                    fs.quarantined[int(i)] = True
        return fs

    def held(self, chunk: int) -> np.ndarray:
        """Boolean [N]: instances excluded from scheduling at ``chunk``
        (quarantined, or still inside their backoff window)."""
        return self.quarantined | (self.hold_until > chunk)


def _damage_checkpoint(root: str) -> int | None:
    """Truncate the newest checkpoint's payload in place (chaos hook).

    Returns the damaged step, or None when there is nothing to damage.
    The manifest's SHA-256 no longer matches, so restore must detect it
    and fall back — this is how ``FaultModel.corrupt_ckpt`` turns into a
    real on-disk fault.
    """
    steps = list_steps(root)
    if not steps:
        return None
    payload = os.path.join(root, f"step_{steps[-1]:09d}", PAYLOAD)
    try:
        size = os.path.getsize(payload)
        with open(payload, "r+b") as f:
            f.truncate(max(size // 2, 1))
    except OSError:
        return None
    return steps[-1]


def _damage_shard(root: str) -> int | None:
    """Truncate the newest committed shard npz in place (chaos hook).

    Returns the damaged shard index, or None. The writer's
    :meth:`~repro.data.shards.DatasetWriter.verify_shards` must detect
    the torn npz, drop the shard, and re-drain its instances.
    """
    import glob

    shards = sorted(glob.glob(os.path.join(root, "shard_*.npz")))
    if not shards:
        return None
    path = shards[-1]
    try:
        size = os.path.getsize(path)
        with open(path, "r+b") as f:
            f.truncate(max(size // 2, 1))
    except OSError:
        return None
    return int(os.path.basename(path)[len("shard_"):-len(".npz")])


def _as_model(faults: FailureInjector | None, n_workers: int) -> FaultModel:
    """Normalize any injector (or None) to a full FaultModel."""
    if faults is None:
        return FaultModel(n_workers, {})
    if isinstance(faults, FaultModel):
        return faults
    return FaultModel(faults.n_workers, faults.plan)


def run_supervised(
    runner: SweepRunner,
    faults: FailureInjector | None = None,
    policy: RetryPolicy | None = None,
    ckpt: CheckpointManager | None = None,
    writer=None,
    journal: RunJournal | None = None,
    state: SweepState | None = None,
    max_chunks: int = 10_000,
    on_progress: Callable[[int, float], None] | None = None,
    chunk_deadline: float | None = None,
    pipeline: bool = False,
) -> tuple[SweepState, dict]:
    """The supervised fault-tolerant run loop — §5.2 without a human.

    Supersedes :func:`repro.core.fault.run_with_failures` for unattended
    runs: same snapshot → run → revert → checkpoint → drain skeleton and
    the same bit-for-bit trajectory guarantees, plus retry budgets with
    exponential backoff, quarantine for poison instances, per-chunk
    durable-state audits and a replayable run journal.

    Per chunk (``c`` = absolute chunk counter, resume-safe):

    1. Terminate when every instance is done or quarantined.
    2. ``runner.run_chunk(state, hold=...)`` — quarantined and
       backing-off instances are planner-held (untouched, never padding).
    3. Inject faults: crashed/hung workers lose their live instances'
       progress (revert to snapshot); poison instances lose only their
       own. Each reverted instance is charged a retry, then either
       quarantined (budget exceeded) or held for ``backoff_chunks``.
       Stragglers keep their results and are only journaled.
    4. Durable writes: checkpoint save, shard drain, then the chaos
       corruption hooks and a :meth:`verify_shards` audit.
    5. Journal the chunk's events (failures first, chunk-commit last) and
       report progress via ``on_progress(c, done_frac)`` — AFTER the
       durable writes, so a kill right after a heartbeat always leaves a
       checkpoint at least as new as the heartbeat.

    ``chunk_deadline`` (seconds of wall clock per chunk) journals a
    "deadline" event on overrun — an in-process jax chunk cannot be
    preempted mid-flight, so genuine hangs are the process controller's
    job (heartbeat-loss SIGKILL, ``repro.launch.controller``); the
    deterministic hang fault (``FaultModel.hangs``) simulates the
    timeout + revert path in-process. ``pipeline=True`` keeps
    :func:`run_with_failures`' double-buffered host I/O: chunk ``c``'s
    durable writes, audits, journal events and heartbeat all happen
    while the devices compute chunk ``c+1``.

    Returns ``(state, info)`` where ``info`` carries ``chunks_run``,
    ``failure_events``, ``completion_rate`` (run_with_failures-compatible)
    plus ``eligible_completion_rate``, ``quarantined`` and the full
    :func:`completion_report`.
    """
    n = runner.cfg.n_instances
    faults = _as_model(faults, runner._n_workers())
    policy = policy or RetryPolicy()
    if state is None:
        state = runner.init()
    fleet = FleetState.zeros(n)
    resumed_events: list[dict] = []
    if journal is not None and journal.exists():
        resumed_events = RunJournal.read(journal.path)
        fleet = FleetState.replay(resumed_events, n)
    if ckpt is not None and ckpt.has_checkpoint():
        state, _meta = ckpt.restore(like=state)
        state = runner._place(state)
        if journal is not None:
            journal.append({
                "kind": "resume",
                "chunk": int(jax.device_get(state.chunk)),
                "skipped_ckpts": list(ckpt.last_skipped),
                "replayed_events": len(resumed_events),
            })

    def _emit(event: dict) -> None:
        if journal is not None:
            journal.append(event)

    chunks_run = 0
    failure_events: list[dict] = []
    # deferred host I/O from the previous chunk (pipeline mode):
    # (chunk id, post-chunk state, drain handle, journal events, done frac)
    deferred: tuple | None = None

    def _flush(packet) -> None:
        if packet is None:
            return
        c, st, handle, events, done_frac = packet
        if ckpt is not None:
            with span("fleet.ckpt", chunk=c):
                ckpt.save(c + 1, st)
                if c in faults.corrupt_ckpt:
                    ckpt.wait()
                    step = _damage_checkpoint(ckpt.root)
                    events = events + [
                        {"kind": "corrupt_ckpt", "chunk": c, "step": step}
                    ]
        if writer is not None:
            with span("fleet.drain", chunk=c):
                if handle is not None:
                    writer.finish_drain(handle)
                else:
                    writer.drain(st)
            with span("fleet.audit", chunk=c):
                if c in faults.corrupt_shard:
                    idx = _damage_shard(writer.root)
                    events = events + [
                        {"kind": "corrupt_shard", "chunk": c, "shard": idx}
                    ]
                repaired = writer.verify_shards()
            if repaired:
                events = events + [
                    {"kind": "shard_repair", "chunk": c, "shards": repaired}
                ]
        with span("fleet.journal", chunk=c):
            for e in events:
                _emit(e)
            _emit({
                "kind": "chunk", "chunk": c, "done": done_frac,
                "quarantined": int(fleet.quarantined.sum()),
            })
        if on_progress is not None:
            on_progress(c, done_frac)

    for _ in range(max_chunks):
        # index fault plans and hold windows by the ABSOLUTE chunk counter
        # so a resumed run replays the same schedule (kill/resume parity)
        with span("fleet.sync"):
            done_host, c = jax.device_get((state.done, state.chunk))
        done_host, c = np.asarray(done_host), int(c)
        if np.all(done_host | fleet.quarantined):
            break
        held = fleet.held(c)
        alive = ~done_host & ~held
        snapshot = state
        t0 = time.monotonic()
        state = runner.run_chunk(state, hold=held if held.any() else None)
        chunks_run += 1

        # ---- fault injection: worker-granular crashes/hangs, then
        # instance-granular poison (only live instances are affected)
        events: list[dict] = []
        mask = np.zeros(n, bool)
        for kind, w in faults.lost_workers(c):
            wm = faults.worker_mask(w, n) & alive
            if wm.any():
                events.append({
                    "kind": "failure", "fault": kind, "chunk": c,
                    "workers": [w],
                    "instances": np.flatnonzero(wm).tolist(),
                })
                mask |= wm
        poison = np.zeros(n, bool)
        for i in faults.poison_instances:
            if 0 <= i < n and alive[i] and not mask[i]:
                poison[i] = True
        if poison.any():
            events.append({
                "kind": "failure", "fault": "poison", "chunk": c,
                "workers": None,
                "instances": np.flatnonzero(poison).tolist(),
            })
            mask |= poison
        slow = faults.straggler_workers(c)
        if slow:
            events.append({
                "kind": "straggler", "chunk": c, "workers": list(slow),
            })
        if mask.any():
            with span("fleet.revert", chunk=c):
                state = revert_instances(state, snapshot, mask)
                state = state._replace(done=state.sim.t >= state.horizon)
            ids = np.flatnonzero(mask)
            fleet.retries[ids] += 1
            over = ids[fleet.retries[ids] > policy.max_retries]
            back = ids[fleet.retries[ids] <= policy.max_retries]
            fleet.quarantined[over] = True
            for i in back:
                fleet.hold_until[i] = c + 1 + policy.backoff_chunks(
                    int(fleet.retries[i])
                )
            # failure events carry POST-update counters so journal replay
            # is plain assignment (FleetState.replay)
            for e in events:
                if e["kind"] != "failure":
                    continue
                e["retries"] = {
                    str(i): int(fleet.retries[i]) for i in e["instances"]
                }
                e["hold_until"] = {
                    str(i): int(fleet.hold_until[i]) for i in e["instances"]
                }
            if over.size:
                events.append({
                    "kind": "quarantine", "chunk": c,
                    "instances": over.tolist(),
                })
            failure_events.extend(
                {k: e[k] for k in ("chunk", "fault", "workers", "instances")}
                for e in events if e["kind"] == "failure"
            )

        if pipeline:
            # chunk c is in flight on the devices; commit chunk c-1's
            # durable state (and its journal/heartbeat) while they compute
            _flush(deferred)
        with span("fleet.sync", chunk=c):
            done_after = np.asarray(jax.device_get(state.done))  # sync point
        elapsed = time.monotonic() - t0
        if chunk_deadline is not None and elapsed > chunk_deadline:
            # an in-flight jax chunk can't be preempted: overruns degrade
            # gracefully to a journaled warning (real hangs are killed by
            # the process controller's heartbeat timeout)
            events.append({
                "kind": "deadline", "chunk": c,
                "elapsed": elapsed, "deadline": chunk_deadline,
            })
        done_frac = float(done_after.mean())
        handle = None
        if pipeline and writer is not None:
            with span("fleet.drain", chunk=c):
                handle = writer.begin_drain(state, done=done_after)
        packet = (c, state, handle, events, done_frac)
        if pipeline:
            deferred = packet
        else:
            _flush(packet)

    _flush(deferred)
    if writer is not None:
        # idempotent close-out: anything a kill window or a shard repair
        # left unpersisted is re-drained here
        with span("fleet.drain"):
            writer.drain(state)

    report = completion_report(state, fleet, runner.cfg.scenarios)
    info = {
        "chunks_run": chunks_run,
        "failure_events": failure_events,
        "completion_rate": report["total"]["completion_rate"],
        "eligible_completion_rate":
            report["total"]["eligible_completion_rate"],
        "quarantined": np.flatnonzero(fleet.quarantined).tolist(),
        "retries_total": int(fleet.retries.sum()),
        "report": report,
    }
    _emit({
        "kind": "complete",
        "chunks_run": chunks_run,
        "completion_rate": info["completion_rate"],
        "eligible_completion_rate": info["eligible_completion_rate"],
        "quarantined": info["quarantined"],
    })
    return state, info


def completion_report(
    state: SweepState,
    fleet: FleetState | None,
    scenarios: tuple[str, ...],
) -> dict:
    """The paper's §5.2 completion-rate accounting, per scenario.

    ``completion_rate`` counts ALL instances (a quarantined instance is a
    failure to complete — the honest headline number);
    ``eligible_completion_rate`` excludes quarantined instances (the
    fleet-health number: did everything we kept scheduling finish?). The
    supervisor's acceptance gate is eligible == 1.0 with every
    quarantined instance explicitly listed.
    """
    done = np.asarray(jax.device_get(state.done))
    sids = np.asarray(jax.device_get(state.scenario_id))
    n = done.size
    if fleet is None:
        fleet = FleetState.zeros(n)

    def _row(sel: np.ndarray, name: str) -> dict:
        total = int(sel.sum())
        completed = int((done & sel).sum())
        quar = int((fleet.quarantined & sel).sum())
        eligible = total - quar
        edone = int((done & sel & ~fleet.quarantined).sum())
        return {
            "scenario": name,
            "instances": total,
            "completed": completed,
            "completion_rate": completed / total if total else 1.0,
            "quarantined": quar,
            "eligible": eligible,
            "eligible_completion_rate":
                edone / eligible if eligible else 1.0,
            "retries": int(fleet.retries[sel].sum()),
        }

    rows = [
        _row(sids == i, name)
        for i, name in enumerate(scenarios)
        if bool((sids == i).any())
    ]
    return {"total": _row(np.ones(n, bool), "total"), "scenarios": rows}


def format_completion_table(report: dict) -> str:
    """Render :func:`completion_report` as the §5.2-style markdown table."""
    header = (
        "| Scenario | Instances | Completed | Completion | "
        "Quarantined | Eligible completion | Retries |\n"
        "|---|---|---|---|---|---|---|"
    )
    lines = [header]
    for row in report["scenarios"] + [report["total"]]:
        lines.append(
            "| {scenario} | {instances} | {completed} | {cr:.1%} | "
            "{quarantined} | {ecr:.1%} | {retries} |".format(
                cr=row["completion_rate"],
                ecr=row["eligible_completion_rate"],
                **{k: row[k] for k in (
                    "scenario", "instances", "completed", "quarantined",
                    "retries",
                )},
            )
        )
    return "\n".join(lines)
