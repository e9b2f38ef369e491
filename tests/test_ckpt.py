"""Checkpoint I/O: roundtrip, atomicity, retention, dtype restore."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypcompat import given, settings, st

from repro.ckpt import CheckpointManager, save_pytree, load_pytree, latest_step
from repro.ckpt.io import load_meta


def _tree(seed=0):
    k = jax.random.key(seed)
    return {
        "w": jax.random.normal(k, (4, 8), jnp.float32),
        "nested": {"b": jnp.arange(5, dtype=jnp.int32)},
        "scalar": jnp.float32(3.5),
    }


def test_roundtrip(tmp_path):
    tree = _tree()
    save_pytree(str(tmp_path / "c"), tree, meta={"step": 7})
    out = load_pytree(str(tmp_path / "c"), like=tree)
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(out)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert load_meta(str(tmp_path / "c"))["step"] == 7


def test_roundtrip_with_shapedtypestruct_like(tmp_path):
    tree = _tree()
    save_pytree(str(tmp_path / "c"), tree)
    like = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree
    )
    out = load_pytree(str(tmp_path / "c"), like=like)
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(out)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_leaf_count_mismatch_raises(tmp_path):
    save_pytree(str(tmp_path / "c"), _tree())
    with pytest.raises(ValueError):
        load_pytree(str(tmp_path / "c"), like={"only": jnp.zeros(3)})


def test_manager_retention_and_latest(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2, async_write=False)
    for s in [1, 2, 3, 4]:
        mgr.save(s, _tree(s))
    assert latest_step(str(tmp_path)) == 4
    kept = sorted(os.listdir(tmp_path))
    assert kept == ["step_000000003", "step_000000004"]
    restored, meta = mgr.restore(like=_tree())
    assert meta["step"] == 4
    for a, b in zip(jax.tree.leaves(_tree(4)), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_manager_async_write_then_restore(tmp_path):
    mgr = CheckpointManager(str(tmp_path), async_write=True)
    mgr.save(10, _tree(10))
    restored, meta = mgr.restore(like=_tree())  # restore barriers on writer
    assert meta["step"] == 10


@settings(max_examples=10, deadline=None)
@given(
    shape=st.tuples(st.integers(1, 5), st.integers(1, 5)),
    dtype=st.sampled_from(["float32", "int32", "bfloat16"]),
)
def test_property_any_shape_dtype_roundtrips(tmp_path_factory, shape, dtype):
    tmp = tmp_path_factory.mktemp("ck")
    x = jnp.ones(shape, dtype=dtype) * 3
    save_pytree(str(tmp / "c"), {"x": x})
    out = load_pytree(str(tmp / "c"), like={"x": x})
    np.testing.assert_array_equal(
        np.asarray(out["x"], dtype=np.float32),
        np.asarray(x, dtype=np.float32),
    )
    assert out["x"].dtype == x.dtype


# --------------------------------------------------------------------------
# crash-atomic saves + integrity-validated restore (the unattended-run
# durable-state contract, paper §5.2)
# --------------------------------------------------------------------------

import json
import shutil

from repro.ckpt import valid_steps, verify_checkpoint
from repro.ckpt.io import MANIFEST, PAYLOAD


def _step_dir(root, step):
    return os.path.join(str(root), f"step_{step:09d}")


def test_verify_checkpoint_detects_truncation_and_missing(tmp_path):
    path = str(tmp_path / "c")
    save_pytree(path, _tree(), meta={"step": 1})
    assert verify_checkpoint(path)
    # truncated payload: digest mismatch
    payload = os.path.join(path, PAYLOAD)
    with open(payload, "r+b") as f:
        f.truncate(os.path.getsize(payload) // 2)
    assert not verify_checkpoint(path)
    # missing payload
    os.remove(payload)
    assert not verify_checkpoint(path)
    # missing / unparseable manifest
    assert not verify_checkpoint(str(tmp_path / "nope"))
    os.makedirs(str(tmp_path / "torn"))
    with open(os.path.join(str(tmp_path / "torn"), MANIFEST), "w") as f:
        f.write('{"leaves": [')
    assert not verify_checkpoint(str(tmp_path / "torn"))


def test_restore_falls_back_past_corrupt_newest(tmp_path):
    """A corrupted newest checkpoint costs one step, never the run: the
    manager skips it (recording the skip) and restores the previous valid
    step; has_checkpoint likewise refuses to count it."""
    mgr = CheckpointManager(str(tmp_path), keep=3, async_write=False)
    for s in (1, 2, 3):
        mgr.save(s, _tree(s))
    payload = os.path.join(_step_dir(tmp_path, 3), PAYLOAD)
    with open(payload, "r+b") as f:
        f.truncate(os.path.getsize(payload) // 2)

    assert valid_steps(str(tmp_path)) == [1, 2]
    assert mgr.has_checkpoint()
    restored, meta = mgr.restore(like=_tree())
    assert meta["step"] == 2
    assert mgr.last_skipped == [3]
    for a, b in zip(jax.tree.leaves(_tree(2)), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_restore_explicit_corrupt_step_is_strict(tmp_path):
    mgr = CheckpointManager(str(tmp_path), async_write=False)
    mgr.save(1, _tree(1))
    mgr.save(2, _tree(2))
    payload = os.path.join(_step_dir(tmp_path, 2), PAYLOAD)
    with open(payload, "r+b") as f:
        f.truncate(1)
    with pytest.raises(FileNotFoundError):
        mgr.restore(like=_tree(), step=2)
    # the newest-valid walk still works
    _, meta = mgr.restore(like=_tree())
    assert meta["step"] == 1


def test_all_checkpoints_corrupt_raises_listing_skips(tmp_path):
    mgr = CheckpointManager(str(tmp_path), async_write=False)
    for s in (1, 2):
        mgr.save(s, _tree(s))
        payload = os.path.join(_step_dir(tmp_path, s), PAYLOAD)
        os.remove(payload)
    assert not mgr.has_checkpoint()
    with pytest.raises(FileNotFoundError) as e:
        mgr.restore(like=_tree())
    assert mgr.last_skipped == [2, 1]
    assert "skipped corrupt steps" in str(e.value)


def test_mid_save_kill_artifacts_are_invisible_and_gced(tmp_path):
    """A staging dir left by a SIGKILLed writer is never mistaken for a
    checkpoint and is swept by the next save's gc."""
    mgr = CheckpointManager(str(tmp_path), async_write=False)
    mgr.save(1, _tree(1))
    # fake a killed writer: stale staging dir with a full payload inside
    stale = os.path.join(str(tmp_path), ".tmp-step_000000002-99999")
    shutil.copytree(_step_dir(tmp_path, 1), stale)
    assert latest_step(str(tmp_path)) == 1  # staging never counts
    restored, meta = mgr.restore(like=_tree())
    assert meta["step"] == 1
    mgr.save(3, _tree(3))
    assert not os.path.exists(stale)  # swept
    assert latest_step(str(tmp_path)) == 3


def test_save_overwrites_same_step_atomically(tmp_path):
    mgr = CheckpointManager(str(tmp_path), async_write=False)
    mgr.save(5, _tree(1))
    mgr.save(5, _tree(2))
    restored, meta = mgr.restore(like=_tree(), step=5)
    assert meta["step"] == 5
    for a, b in zip(jax.tree.leaves(_tree(2)), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_legacy_digestless_manifest_still_verifies(tmp_path):
    """Pre-digest checkpoints (no payload_sha256 key) must keep restoring:
    verification skips the digest check instead of rejecting them."""
    path = str(tmp_path / "c")
    save_pytree(path, _tree(), meta={"step": 1})
    mpath = os.path.join(path, MANIFEST)
    with open(mpath) as f:
        manifest = json.load(f)
    del manifest["payload_sha256"]
    with open(mpath, "w") as f:
        json.dump(manifest, f)
    assert verify_checkpoint(path)
    load_pytree(path, like=_tree())


def test_restore_skips_bit_flipped_legacy_payload(tmp_path):
    """A digestless (legacy) step with one flipped payload bit fails
    verification through the zip CRC, so restore falls back to the older
    step instead of raising at load."""
    import struct
    import zipfile

    mgr = CheckpointManager(str(tmp_path), async_write=False)
    for s in (1, 2):
        mgr.save(s, _tree(s))
    path = _step_dir(tmp_path, 2)
    mpath = os.path.join(path, MANIFEST)
    with open(mpath) as f:
        manifest = json.load(f)
    del manifest["payload_sha256"]
    with open(mpath, "w") as f:
        json.dump(manifest, f)
    payload = os.path.join(path, PAYLOAD)
    with zipfile.ZipFile(payload) as zf:
        info = zf.getinfo("arr_0.npy")
    with open(payload, "r+b") as f:
        f.seek(info.header_offset + 26)     # local header: name, extra lens
        name_len, extra_len = struct.unpack("<HH", f.read(4))
        last = (info.header_offset + 30 + name_len + extra_len
                + info.compress_size - 1)   # last byte of the leaf's data
        f.seek(last)
        byte = f.read(1)[0]
        f.seek(last)
        f.write(bytes([byte ^ 1]))
    assert not verify_checkpoint(path)
    restored, meta = mgr.restore(like=_tree())
    assert meta["step"] == 1
    assert mgr.last_skipped == [2]
    for a, b in zip(jax.tree.leaves(_tree(1)), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_restore_falls_back_past_structure_drift(tmp_path):
    """A newest step whose leaves no longer fit the target structure is
    skipped like a corrupt one; the older step that fits restores."""
    mgr = CheckpointManager(str(tmp_path), async_write=False)
    mgr.save(1, _tree(1))
    mgr.save(2, {"only": jnp.zeros(3)})
    restored, meta = mgr.restore(like=_tree())
    assert meta["step"] == 1
    assert mgr.last_skipped == [2]
    for a, b in zip(jax.tree.leaves(_tree(1)), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("error", [
    jax.errors.JaxRuntimeError("device lost during transfer"),
    ValueError("sharding does not divide the array's leading dim"),
])
def test_restore_device_error_raises_instead_of_falling_back(tmp_path,
                                                            monkeypatch,
                                                            error):
    """A device or placement failure during load is not checkpoint damage:
    restore must raise it rather than silently resume an older step."""
    import repro.ckpt.manager as manager

    mgr = CheckpointManager(str(tmp_path), async_write=False)
    for s in (1, 2):
        mgr.save(s, _tree(s))

    def broken_load(path, like, shardings=None):
        raise error

    monkeypatch.setattr(manager, "load_pytree", broken_load)
    with pytest.raises(type(error), match=str(error)):
        mgr.restore(like=_tree())
    assert mgr.last_skipped == []
