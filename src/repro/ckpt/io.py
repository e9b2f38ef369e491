"""Pytree checkpoint I/O: numpy payloads + JSON manifest.

Checkpoints are stored logically (full arrays, flatten-order indexed), so a
restore can re-shard onto a *different* mesh than the one that saved — the
elastic-scaling requirement (DESIGN.md §7). Writes are crash-atomic
(tmp-file + fsync + rename, manifest committed last) and the manifest
carries a SHA-256 digest of the payload, so a kill mid-write or a torn /
bit-rotted payload is *detected* at restore time instead of silently
loaded — the durable-state half of the paper's 100 % completion
accounting (§5.2). :func:`verify_checkpoint` / :func:`valid_steps` are
the audit surface the unattended-run controller and the hardened
:class:`~repro.ckpt.manager.CheckpointManager` restore path key on.
"""

from __future__ import annotations

import hashlib
import json
import os
import tempfile
import zipfile
from typing import Any

import jax
import numpy as np

from repro.utils.tree import tree_flatten_with_paths

MANIFEST = "manifest.json"
PAYLOAD = "arrays.npz"

# dtypes numpy's npz can't roundtrip natively → stored as raw same-width ints
_EXOTIC_AS_RAW = {
    "bfloat16": np.uint16,
    "float8_e4m3fn": np.uint8,
    "float8_e5m2": np.uint8,
}


def _to_storable(arr: np.ndarray) -> np.ndarray:
    raw = _EXOTIC_AS_RAW.get(str(arr.dtype))
    return arr.view(raw) if raw is not None else arr


def _from_storable(arr: np.ndarray, logical_dtype: str) -> np.ndarray:
    if logical_dtype in _EXOTIC_AS_RAW:
        import ml_dtypes

        return arr.view(np.dtype(getattr(ml_dtypes, logical_dtype)))
    return arr


def _is_prng_key(x: Any) -> bool:
    try:
        return jax.dtypes.issubdtype(x.dtype, jax.dtypes.prng_key)
    except (AttributeError, TypeError):
        return False


def fsync_file(path: str) -> None:
    fd = os.open(path, os.O_RDONLY)
    try:
        os.fsync(fd)
    finally:
        os.close(fd)


def fsync_dir(path: str) -> None:
    """Flush a directory entry (the rename itself) to disk; best-effort on
    filesystems that reject directory fsync."""
    try:
        fd = os.open(path, os.O_RDONLY)
    except OSError:
        return
    try:
        os.fsync(fd)
    except OSError:
        pass
    finally:
        os.close(fd)


def _sha256_file(path: str) -> str:
    h = hashlib.sha256()
    with open(path, "rb") as f:
        for block in iter(lambda: f.read(1 << 20), b""):
            h.update(block)
    return h.hexdigest()


def save_pytree(path: str, tree: Any, meta: dict | None = None) -> None:
    """Crash-atomically save all array leaves of ``tree`` under ``path``.

    Commit protocol: payload npz is written to a temp name, fsynced and
    renamed into place; the manifest (which embeds the payload's SHA-256)
    follows the same way. The manifest is therefore the commit point — a
    kill at any moment leaves either no manifest (checkpoint invisible)
    or a manifest whose digest vouches for a fully-written payload.
    """
    os.makedirs(path, exist_ok=True)
    named = tree_flatten_with_paths(tree)
    arrays = {}
    index = []
    for i, (p, leaf) in enumerate(named):
        entry = {"path": p}
        if _is_prng_key(leaf):
            entry["prng_impl"] = str(jax.random.key_impl(leaf))
            leaf = jax.random.key_data(leaf)
        arr = np.asarray(jax.device_get(leaf))
        arrays[f"arr_{i}"] = _to_storable(arr)
        entry.update(shape=list(arr.shape), dtype=str(arr.dtype))
        index.append(entry)

    fd, tmp = tempfile.mkstemp(dir=path, suffix=".tmp.npz")
    os.close(fd)
    np.savez(tmp, **arrays)
    fsync_file(tmp)
    os.replace(tmp, os.path.join(path, PAYLOAD))

    manifest = {
        "leaves": index,
        "meta": meta or {},
        "payload_sha256": _sha256_file(os.path.join(path, PAYLOAD)),
        "n_leaves": len(index),
    }
    fd, tmp = tempfile.mkstemp(dir=path, suffix=".json.tmp")
    with os.fdopen(fd, "w") as f:
        json.dump(manifest, f)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, os.path.join(path, MANIFEST))
    fsync_dir(path)


def verify_checkpoint(path: str) -> bool:
    """True iff the checkpoint directory at ``path`` is complete and intact.

    Checks, cheapest first: manifest present and parseable, payload
    present, payload SHA-256 matches the manifest's recorded digest, and
    the npz carries every indexed leaf. Legacy manifests without a digest
    instead have every member read, so that the zip CRC vouches for the
    bytes. A kill mid-save, a truncated payload or a flipped bit all fail
    here instead of at (or worse, after) load time.
    """
    try:
        with open(os.path.join(path, MANIFEST)) as f:
            manifest = json.load(f)
        payload = os.path.join(path, PAYLOAD)
        if not os.path.exists(payload):
            return False
        digest = manifest.get("payload_sha256")
        if digest is not None and _sha256_file(payload) != digest:
            return False
        with np.load(payload) as z:
            names = set(z.files)
            if digest is None:
                for name in names:
                    z[name]
        return all(f"arr_{i}" in names
                   for i in range(len(manifest["leaves"])))
    except (OSError, ValueError, KeyError, EOFError, zipfile.BadZipFile):
        return False


class StructureMismatch(ValueError):
    """A checkpoint's leaves do not fit the target structure."""


def load_pytree(path: str, like: Any, shardings: Any = None) -> Any:
    """Load a checkpoint into the structure of ``like``.

    ``like`` may hold concrete arrays or ShapeDtypeStructs; only its treedef
    and leaf dtypes are used. If ``shardings`` (a matching pytree of
    ``jax.sharding.Sharding`` or None leaves) is given, each leaf is placed
    with that sharding — this is where elastic re-meshing happens. Raises
    :class:`StructureMismatch` when the leaf counts differ.
    """
    with open(os.path.join(path, MANIFEST)) as f:
        manifest = json.load(f)
    payload = np.load(os.path.join(path, PAYLOAD))
    leaves_like, treedef = jax.tree.flatten(like)
    n = len(manifest["leaves"])
    if n != len(leaves_like):
        raise StructureMismatch(
            f"checkpoint has {n} leaves but target structure has "
            f"{len(leaves_like)}"
        )
    out = []
    shard_leaves = (
        jax.tree.flatten(shardings)[0] if shardings is not None
        else [None] * n
    )
    for i, (ref, shard) in enumerate(zip(leaves_like, shard_leaves)):
        entry = manifest["leaves"][i]
        arr = _from_storable(payload[f"arr_{i}"], entry["dtype"])
        if "prng_impl" in entry:
            key = jax.random.wrap_key_data(
                jax.numpy.asarray(arr), impl=entry["prng_impl"]
            )
            out.append(key)
            continue
        want = np.dtype(getattr(ref, "dtype", arr.dtype))
        if arr.dtype != want:
            arr = arr.astype(want)
        if shard is not None:
            out.append(jax.device_put(arr, shard))
        else:
            out.append(jax.numpy.asarray(arr))
    return jax.tree.unflatten(treedef, out)


def load_meta(path: str) -> dict:
    with open(os.path.join(path, MANIFEST)) as f:
        return json.load(f)["meta"]


def list_steps(root: str) -> list[int]:
    """All step indices with a committed manifest under ``root``, ascending
    (cheap scan — no payload verification; see :func:`valid_steps`)."""
    if not os.path.isdir(root):
        return []
    steps = []
    for name in os.listdir(root):
        if name.startswith("step_"):
            if os.path.exists(os.path.join(root, name, MANIFEST)):
                try:
                    steps.append(int(name.split("_", 1)[1]))
                except ValueError:
                    pass
    return sorted(steps)


def latest_step(root: str) -> int | None:
    """Highest step among ``root/step_*`` checkpoint dirs, or None."""
    steps = list_steps(root)
    return steps[-1] if steps else None


def valid_steps(root: str) -> list[int]:
    """Step indices whose checkpoint passes :func:`verify_checkpoint`,
    ascending — the restore-candidate list a kill mid-save can't poison."""
    return [
        s for s in list_steps(root)
        if verify_checkpoint(os.path.join(root, f"step_{s:09d}"))
    ]
