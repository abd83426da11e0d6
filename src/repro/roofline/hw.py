"""Per-chip peaks, keyed by ``jax.Device.device_kind``.

A device kind missing from :data:`PEAKS` is an error, never a default: a
roofline share against another chip's peaks is a wrong number. Planning
paths that target a chip which is not attached (the production dry-run)
name its kind explicitly.
"""

from __future__ import annotations

import dataclasses

__all__ = ["ChipPeaks", "PEAKS", "V5E", "peaks"]


@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    flops_bf16: float  # FLOP/s per chip
    hbm_bw: float  # bytes/s per chip
    ici_link_bw: float  # bytes/s per inter-chip link
    source: str


V5E = "TPU v5 lite"  # what jax reports as device_kind for a TPU v5e chip

PEAKS = {
    V5E: ChipPeaks(
        flops_bf16=197e12,
        hbm_bw=819e9,
        # 1,600 Gbit/s of interconnect per chip over 4 links
        ici_link_bw=50e9,
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
