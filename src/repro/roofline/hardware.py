"""Published per-chip peaks for the roofline model, keyed by device kind.

Look a chip up by what JAX reports as ``jax.Device.device_kind``.  A kind
that is not in the table raises: a roofline share against another chip's
peaks would be a wrong number, not an approximate one.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    flops_bf16: float        # FLOP/s per chip
    hbm_bw: float            # bytes/s per chip
    hbm_bytes: int           # bytes per chip
    ici_bw_per_link: float   # bytes/s per link
    source: str


#: Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB of HBM
#: at 819 GB/s, 1,600 Gbit/s of interchip interconnect over 4 links.
TPU_V5E = ChipPeaks(
    flops_bf16=197e12, hbm_bw=819e9, hbm_bytes=16 * 10**9,
    ici_bw_per_link=1600e9 / 8 / 4,
    source='Google Cloud documentation, "TPU v5e"')

#: keyed by ``device_kind``; JAX names the v5e "TPU v5 lite".
PEAKS = {"TPU v5 lite": TPU_V5E}

#: DCN (cross-pod) egress per host, bytes/s: an assumed conservative
#: ~25 Gbit/s, not a published figure; used for the dry run's "pod" axis.
DCN_BW_PER_HOST = 25e9 / 8


def peaks(device_kind: str) -> ChipPeaks:
    """The published peaks of ``device_kind``; unknown kinds raise."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None
