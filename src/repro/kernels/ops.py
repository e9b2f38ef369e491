"""Public jit'd wrappers for the Pallas kernels (the ops layer).

Off-TPU the kernels run with ``interpret=True``; on TPU hardware the same
calls compile to Mosaic. ``interpret`` defaults to True when no TPU is
present so examples/tests work everywhere.
"""

from __future__ import annotations

import jax

from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.rglru import rglru_linear_scan as _rglru
from repro.kernels.rwkv6 import wkv6 as _wkv6
from repro.kernels.idm import neighbor_kernel as _neighbor


def _default_interpret() -> bool:
    return jax.default_backend() != "tpu"


def flash_attention(q, k, v, *, causal=True, window=0, softcap=0.0,
                    scale=None, block_q=128, block_k=128, interpret=None):
    interpret = _default_interpret() if interpret is None else interpret
    return _flash(
        q, k, v, causal=causal, window=window, softcap=softcap, scale=scale,
        block_q=block_q, block_k=block_k, interpret=interpret,
    )


def rglru_linear_scan(a, x, h0, *, block_s=256, block_w=512, interpret=None):
    interpret = _default_interpret() if interpret is None else interpret
    return _rglru(
        a, x, h0, block_s=block_s, block_w=block_w, interpret=interpret
    )


def wkv6(r, k, v, w, u, s0, *, block_s=128, interpret=None):
    interpret = _default_interpret() if interpret is None else interpret
    return _wkv6(r, k, v, w, u, s0, block_s=block_s, interpret=interpret)


def neighbor_kernel(pos, lane, active, query_lanes,
                    *, veh_len=4.5, block=128, interpret=None):
    interpret = _default_interpret() if interpret is None else interpret
    return _neighbor(
        pos, lane, active, query_lanes,
        veh_len=veh_len, block=block, interpret=interpret,
    )
