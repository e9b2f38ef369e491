"""Checkpoint manager: step-indexed directories, retention, async writes.

Writes happen on a background thread (the paper's jobs checkpoint at slice
boundaries; training must not stall on I/O), with a barrier before the next
write or restore so at most one write is in flight.

Crash safety (the unattended-run contract, paper §5.2): each save is
staged into a hidden ``.tmp-step_*`` directory, fsynced, and renamed into
place in one atomic directory move — a SIGKILL at any instant leaves
either the previous checkpoint set untouched or the new step fully
committed, never a half-written ``step_*`` dir. Restore only considers
checkpoints that pass :func:`repro.ckpt.io.verify_checkpoint` (manifest
present, payload SHA-256 matches) and automatically falls back past a
corrupt or torn newest checkpoint to the most recent valid one, recording
what it skipped in :attr:`last_skipped`.
"""

from __future__ import annotations

import os
import shutil
import threading
from typing import Any

import jax

from repro.ckpt.io import (
    StructureMismatch,
    save_pytree,
    load_pytree,
    load_meta,
    fsync_dir,
    list_steps,
    verify_checkpoint,
)

_TMP_PREFIX = ".tmp-step_"


class CheckpointManager:
    def __init__(
        self,
        root: str,
        keep: int = 3,
        async_write: bool = True,
    ) -> None:
        self.root = root
        self.keep = keep
        self.async_write = async_write
        self._thread: threading.Thread | None = None
        # steps the last restore() walk rejected (corrupt/torn), newest
        # first — the run journal surfaces these as ckpt_skipped events
        self.last_skipped: list[int] = []
        os.makedirs(root, exist_ok=True)

    def _dir(self, step: int) -> str:
        return os.path.join(self.root, f"step_{step:09d}")

    def wait(self) -> None:
        if self._thread is not None:
            self._thread.join()
            self._thread = None

    def save(self, step: int, tree: Any, meta: dict | None = None) -> None:
        self.wait()
        # materialize on host *before* handing to the writer thread so the
        # caller may donate/overwrite device buffers immediately
        host_tree = jax.tree.map(lambda x: jax.device_get(x), tree)
        meta = dict(meta or {}, step=step)

        def _write() -> None:
            # stage → fsync → rename: the step dir appears atomically, so
            # a kill mid-save can never produce a half-written step_* dir
            final = self._dir(step)
            tmp = os.path.join(
                self.root, f"{_TMP_PREFIX}{step:09d}-{os.getpid()}"
            )
            shutil.rmtree(tmp, ignore_errors=True)
            save_pytree(tmp, host_tree, meta)
            if os.path.isdir(final):
                shutil.rmtree(final)
            os.replace(tmp, final)
            fsync_dir(self.root)
            self._gc()

        if self.async_write:
            self._thread = threading.Thread(target=_write, daemon=True)
            self._thread.start()
        else:
            _write()

    def restore(
        self, like: Any, step: int | None = None, shardings: Any = None
    ) -> tuple[Any, dict]:
        """Load the newest *valid* checkpoint (or ``step`` exactly).

        With ``step=None`` the manager walks committed steps newest-first,
        skipping any directory that fails integrity verification or whose
        leaves no longer match ``like`` — a kill mid-save or a corrupted
        write costs at most one step of progress, never the run. Skipped
        steps land in :attr:`last_skipped`. Any other load error (a device
        or placement failure) raises: it is not damage to that step, and
        an older step would hide it. An explicit ``step`` is strict: a
        corrupt target raises instead of silently loading garbage.
        """
        self.wait()
        self.last_skipped = []
        if step is not None:
            path = self._dir(step)
            if not verify_checkpoint(path):
                raise FileNotFoundError(
                    f"checkpoint step {step} at {path} is missing or fails "
                    "integrity verification"
                )
            return load_pytree(path, like, shardings), load_meta(path)
        for s in sorted(list_steps(self.root), reverse=True):
            path = self._dir(s)
            if not verify_checkpoint(path):
                self.last_skipped.append(s)
                continue
            try:
                return load_pytree(path, like, shardings), load_meta(path)
            except (StructureMismatch, KeyError):
                # digest said intact but the leaves don't fit ``like``
                # (structure drift) — fall back to the next-oldest step
                self.last_skipped.append(s)
        raise FileNotFoundError(
            f"no valid checkpoints under {self.root}"
            + (f" (skipped corrupt steps {self.last_skipped})"
               if self.last_skipped else "")
        )

    def has_checkpoint(self) -> bool:
        """True iff at least one checkpoint passes integrity verification
        — an incomplete or corrupted save never counts as resumable."""
        self.wait()
        return any(
            verify_checkpoint(self._dir(s)) for s in list_steps(self.root)
        )

    def _gc(self) -> None:
        steps = sorted(
            int(n.split("_", 1)[1])
            for n in os.listdir(self.root)
            if n.startswith("step_")
        )
        for s in steps[: -self.keep]:
            shutil.rmtree(self._dir(s), ignore_errors=True)
        # stale staging dirs from a killed writer are dead weight: only
        # this process's in-flight tmp (none, _gc runs post-rename) is live
        for n in os.listdir(self.root):
            if n.startswith(_TMP_PREFIX):
                shutil.rmtree(os.path.join(self.root, n),
                              ignore_errors=True)
