"""Pallas TPU kernels for the framework's compute hot spots.

Each kernel has: ``<name>.py`` (pl.pallas_call + BlockSpec), a jit'd wrapper
in ``ops.py``, and a pure-jnp oracle in ``ref.py``. Tests sweep shapes/dtypes
in ``interpret=True`` mode and assert_allclose against the oracles.

Kernels:
- ``flash_attention`` — tiled online-softmax attention (causal / sliding
  window / GQA / Gemma-2 logit softcap). TPU serving+prefill path.
- ``rglru``           — RG-LRU linear recurrence, sequence-tiled with carried
  state (recurrentgemma).
- ``rwkv6``           — WKV6 recurrence with data-dependent decay.
- ``idm``             — the multi-query lead+follower ``neighbor_kernel``
  (the physics hot spot the paper delegates to Webots) backing the
  neighborhood engine (``repro.core.neighbors``).
"""

from repro.kernels.ops import (
    flash_attention,
    rglru_linear_scan,
    wkv6,
    neighbor_kernel,
)

__all__ = [
    "flash_attention",
    "rglru_linear_scan",
    "wkv6",
    "neighbor_kernel",
]
