"""Mesh construction + host-platform device forcing — the sharding layer.

Everything here is a FUNCTION, not a module-level constant: importing this
module never touches jax device state (the dry-run sets XLA_FLAGS before
any jax import; smoke tests and benches must keep seeing the default
device set).

The sweep executor (:mod:`repro.core.sweep`) is written against an
abstract 1-D instance mesh, so the same code path covers:

- one CPU process pretending to be N devices
  (``XLA_FLAGS=--xla_force_host_platform_device_count=N``, or the
  launcher's ``--devices N`` which sets it for you —
  :func:`force_host_device_count`), the paper's "multiple instances per
  node" on a laptop;
- a real multi-device host (N GPUs / TPU chips): identical code, real
  parallel speedup.
"""

from __future__ import annotations

import os

import jax
import numpy as np

_FORCE_FLAG = "--xla_force_host_platform_device_count"


def force_host_device_count(n: int) -> None:
    """Make the CPU backend expose ``n`` devices (simulated-device mode).

    Rewrites the ``XLA_FLAGS`` env var, replacing any existing
    ``--xla_force_host_platform_device_count`` setting. MUST run before
    jax initializes its backends (i.e. before the first array op or
    ``jax.devices()`` call — merely importing jax is fine); afterwards the
    flag is silently ignored by XLA, so launchers call this from argv
    pre-parsing before importing anything heavy
    (see :mod:`repro.launch.sweep`). Affects only the host (CPU) platform;
    harmless on real accelerator backends.
    """
    if n < 1:
        raise ValueError(f"device count must be >= 1, got {n}")
    flags = [
        f for f in os.environ.get("XLA_FLAGS", "").split()
        if not f.startswith(_FORCE_FLAG)
    ]
    flags.append(f"{_FORCE_FLAG}={n}")
    os.environ["XLA_FLAGS"] = " ".join(flags)


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 = 256 chips/pod (TPU v5e); 2 pods adds the leading 'pod' axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )


def make_host_mesh(max_workers: int | None = None):
    """Available devices as a 1-D 'workers' mesh — the sweep mesh.

    ``max_workers`` caps the device count (uses the first k devices) so a
    launcher's ``--devices`` flag actually sizes the mesh the sweep runs
    on, not just its failure-injection bookkeeping. Raises when more
    devices are requested than the backend exposes (on CPU, call
    :func:`force_host_device_count` before jax initializes — the
    launcher's ``--devices`` does).
    """
    devs = list(jax.devices())
    if max_workers is not None:
        if max_workers > len(devs):
            raise ValueError(
                f"{max_workers} devices requested but only {len(devs)} "
                f"available — on CPU, force more with "
                f"XLA_FLAGS={_FORCE_FLAG}=N (or the sweep launcher's "
                f"--devices N) before jax initializes"
            )
        devs = devs[: max(1, max_workers)]
    return jax.sharding.Mesh(np.asarray(devs), ("workers",))


def instance_sharding(mesh):
    """The sweep's canonical sharding: instance axis over every mesh axis.

    Re-exported from :mod:`repro.core.sweep` so launchers and benchmarks
    can place arrays the way the executor expects without importing core
    internals.
    """
    from repro.core.sweep import instance_sharding as _impl

    return _impl(mesh)


def make_abstract_mesh(shape, axes):
    """Device-free mesh for spec validation (tests, dry-run planning)."""
    return jax.sharding.AbstractMesh(tuple(shape), tuple(axes))
