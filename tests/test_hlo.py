"""HLO collective parser: synthetic lines + a real lowered program."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.utils.hlo import collective_bytes


def test_explicit_groups_all_reduce():
    hlo = (
        "%ar = f32[128,256]{1,0} all-reduce(f32[128,256]{1,0} %x), "
        "replica_groups={{0,1,2,3}}, to_apply=%sum"
    )
    st = collective_bytes(hlo)
    assert st.counts["all-reduce"] == 1
    payload = 128 * 256 * 4
    assert st.payload_bytes["all-reduce"] == payload
    np.testing.assert_allclose(
        st.wire_bytes["all-reduce"], 2 * payload * 3 / 4
    )


def test_iota_groups_all_gather():
    hlo = (
        "%ag = bf16[16,4096]{1,0} all-gather(bf16[1,4096]{1,0} %x), "
        "replica_groups=[32,16]<=[512], dimensions={0}"
    )
    st = collective_bytes(hlo)
    assert st.counts["all-gather"] == 1
    out_bytes = 16 * 4096 * 2
    np.testing.assert_allclose(
        st.wire_bytes["all-gather"], out_bytes * 15 / 16
    )


def test_reduce_scatter_uses_input_bytes():
    hlo = (
        "%rs = f32[8,128]{1,0} reduce-scatter(f32[64,128]{1,0} %x), "
        "replica_groups={{0,1,2,3,4,5,6,7}}, dimensions={0}, to_apply=%sum"
    )
    st = collective_bytes(hlo)
    in_bytes = 64 * 128 * 4
    np.testing.assert_allclose(
        st.wire_bytes["reduce-scatter"], in_bytes * 7 / 8
    )


def test_collective_permute_full_buffer():
    hlo = (
        "%cp = bf16[1024]{0} collective-permute(bf16[1024]{0} %x), "
        "source_target_pairs={{0,1},{1,0}}"
    )
    st = collective_bytes(hlo)
    assert st.wire_bytes["collective-permute"] == 1024 * 2


def test_done_ops_not_double_counted():
    hlo = "\n".join([
        "%s = f32[256]{0} all-reduce-start(f32[256]{0} %x), "
        "replica_groups={{0,1}}, to_apply=%sum",
        "%d = f32[256]{0} all-reduce-done(f32[256]{0} %s)",
    ])
    st = collective_bytes(hlo)
    assert st.counts.get("all-reduce", 0) == 1


def test_non_collective_lines_ignored():
    st = collective_bytes(
        "%add = f32[4]{0} add(f32[4]{0} %a, f32[4]{0} %b)"
    )
    assert st.total_wire_bytes == 0.0


def test_real_lowered_program_has_allreduce():
    """psum under shard_map must surface in the parsed stats."""
    if len(jax.devices()) < 1:
        pytest.skip("no devices")
    mesh = jax.make_mesh((1,), ("x",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    from jax.sharding import PartitionSpec as P

    def f(a):
        return jax.lax.psum(a, "x")

    g = jax.jit(
        jax.shard_map(f, mesh=mesh, in_specs=P("x"), out_specs=P())
    )
    hlo = g.lower(jnp.ones((8, 8))).compile().as_text()
    st = collective_bytes(hlo)
    assert st.counts.get("all-reduce", 0) >= 1
