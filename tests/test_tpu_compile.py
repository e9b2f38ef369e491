"""Compile the sweep's main path for a TPU v5e that is described, not attached.

The TPU compiler is installed next to JAX, so these tests lower and compile
for a ``v5e:2x2`` topology description with no chip present. They catch what
interpret mode cannot: block shapes Mosaic refuses, kernels silently lowered
in interpret mode, programs that do not fit a chip's memory.

Only one process at a time may load the TPU library, so the topology is
described inside a module-scoped fixture (never at import) and every such
test lives in this one file: under pytest-xdist, the one worker given this
file loads the library and the others never try.
"""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.core.neighbors import TPU_DENSE_MAX_SLOTS, resolve_impl
from repro.core.record import RecordConfig
from repro.core.scenario import SimConfig
from repro.core.scenarios import list_scenarios
from repro.core.sweep import SweepConfig, SweepRunner
from repro.kernels.idm import neighbor_kernel

HBM_BYTES = 16 * 2**30  # one v5e chip


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler / library held elsewhere
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def _no_persistent_cache():
    # a compile for a described chip is written to the cache but cannot be
    # read back without one; keep these compiles out of it (the cache
    # latches its on/off decision, hence the resets)
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _cfg(n_instances, n_slots, mix=None, impl="sort"):
    return SweepConfig(
        n_instances=n_instances, steps_per_instance=1200, chunk_steps=400,
        sim=SimConfig(n_slots=n_slots, neighbor_impl=impl),
        vary_horizon=True,
        scenario_mix=tuple(list_scenarios()) if mix is None else mix,
        record=RecordConfig(record_every=10, k_slots=8),
    )


def _chunk_args(runner, sharding):
    """Shapes of one full-width chunk call, placed with ``sharding``."""
    st = jax.eval_shape(runner.init)

    def place(s):
        return jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding)

    return jax.tree.map(
        place, (st.sim, st.metrics, st.params, st.horizon, st.trace)
    ), place(st.scenario_id)


@pytest.mark.parametrize("q", [1, 4])
@pytest.mark.parametrize("n", [48, 64, 256, 512])
def test_neighbor_kernel_compiles_with_mosaic(one_chip, n, q):
    """The engine's kernel at the simulator's slot counts, for one query
    vector (``query_lanes``) and one per lane (``build_tables``)."""
    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    fn = jax.jit(lambda p, l, a, ql: neighbor_kernel(p, l, a, ql,
                                                     interpret=False))
    compiled = fn.lower(
        s((n,), jnp.float32), s((n,), jnp.int32), s((n,), jnp.bool_),
        s((q, n), jnp.int32),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_sort_chunk_program_fits_one_chip(one_chip):
    """One grouped 400-step chunk of the ``sort`` engine (what ``auto``
    picks on CPU hosts and above 2048 slots), recording on, at 256
    instances x 256 slots."""
    runner = SweepRunner(_cfg(256, 256))
    args, _ = _chunk_args(runner, one_chip)
    compiled = runner._roster_fns[0].lower(*args).compile()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert 0 < used < HBM_BYTES


def test_dense_chunk_program_fits_one_chip_at_its_largest(one_chip):
    """The engine ``auto`` picks on a TPU at the largest slot count it
    picks it for: one 400-step chunk of 48 instances, recording on."""
    n_slots = TPU_DENSE_MAX_SLOTS
    assert resolve_impl("auto", n_slots, "tpu") == "dense"
    runner = SweepRunner(_cfg(48, n_slots, mix=("highway_merge",),
                              impl="dense"))
    args, _ = _chunk_args(runner, one_chip)
    mem = runner._roster_fns[0].lower(*args).compile().memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert 0 < used < HBM_BYTES


def _assert_block_program_has_no_collectives(topo, impl):
    mesh = jax.sharding.Mesh(np.asarray(topo.devices), ("workers",))
    runner = SweepRunner(_cfg(64, 64, mix=("highway_merge",), impl=impl),
                         mesh=mesh)
    spread = NamedSharding(mesh, P("workers"))
    args, row_sid = _chunk_args(runner, spread)
    block_sid = jax.ShapeDtypeStruct((4,), jnp.int32, sharding=spread)
    text = runner._block_fn_uniform.lower(
        *args, row_sid, block_sid
    ).compile().as_text()
    for op in ("all-reduce", "all-gather", "all-to-all",
               "collective-permute", "reduce-scatter"):
        assert op not in text, op


def test_four_chip_block_program_has_no_collectives(topo):
    """The D=4 executor compiles as one program with no cross-chip
    traffic inside the chunk."""
    _assert_block_program_has_no_collectives(topo, "sort")


def test_four_chip_dense_block_program_has_no_collectives(topo):
    """The same on ``dense``, the engine ``auto`` picks for the four-chip
    cell's 64 slots on a TPU."""
    assert resolve_impl("auto", 64, "tpu") == "dense"
    _assert_block_program_has_no_collectives(topo, "dense")


def test_controller_import_leaves_jax_unloaded():
    """The process supervisor must never hold the chip: importing it in a
    fresh interpreter loads no jax."""
    code = (
        "import sys, repro.launch.controller; "
        "sys.exit('jax' in sys.modules)"
    )
    src = os.path.join(os.path.dirname(os.path.dirname(__file__)), "src")
    env = dict(os.environ, PYTHONPATH=src)
    out = subprocess.run([sys.executable, "-c", code], env=env, timeout=120)
    assert out.returncode == 0
