"""Published peaks of one chip, keyed by ``jax.Device.device_kind``.

A device kind that is not in the table is an error, never a default.
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": 'Google Cloud documentation, "TPU v5e"',
    },
}


def peak(device_kind: str) -> dict:
    """The published peaks of one chip of ``device_kind``."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peak for device kind {device_kind!r}; known: {sorted(PEAKS)}"
        ) from None
