"""Published peaks of the chips the benchmark runs on, by JAX's
``device_kind``. Source: Google Cloud documentation, "TPU v5e" (system
architecture: per-chip peak compute, HBM capacity and bandwidth,
inter-chip interconnect). A chip that is not here is an error, never a
default: a roofline share against the wrong peak is a wrong number.
"""

from __future__ import annotations

SOURCE = "Google Cloud documentation, TPU v5e system architecture"

PEAKS = {
    "TPU v5 lite": {
        "bf16_flop_per_s": 197e12,
        "int8_op_per_s": 393e12,
        "hbm_bytes": 16e9,
        "hbm_bytes_per_s": 819e9,
        "ici_bits_per_s": 1600e9,
    },
}


def of(device_kind: str) -> dict:
    """The peak table row of ``device_kind``; KeyError for an unknown chip."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None
