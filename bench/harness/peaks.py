"""Per-chip peaks, keyed by ``jax.Device.device_kind``.

A device kind missing from :data:`PEAKS` is an error, never a default: a share
of another chip's peak is a wrong number.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    flops_bf16: float  # FLOP/s per chip
    hbm_bw: float  # bytes/s per chip
    hbm_bytes: float  # device memory per chip
    source: str


PEAKS = {
    "TPU v5 lite": ChipPeaks(
        flops_bf16=197e12,
        hbm_bw=819e9,
        hbm_bytes=16e9,
        source='Google Cloud documentation, "TPU v5e"',
    ),
}


def peaks(device_kind: str) -> ChipPeaks:
    """Peaks of the chip ``device_kind`` names; raises ``KeyError`` when the
    table does not know it."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no peaks for device kind {device_kind!r}; known: {sorted(PEAKS)}"
        ) from None
