"""Published per-chip peaks (the roofline denominators), keyed by the
``device_kind`` JAX reports.  A device kind that is not in the table is
an error, never a default.

TPU v5e — Google Cloud documentation, "TPU v5e" (system architecture):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB of HBM at 819 GB/s, and
1,600 Gbit/s of inter-chip interconnect per chip (four links).
"""

from __future__ import annotations

import dataclasses
from typing import Dict


@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    bf16_flops: float      # FLOP/s per chip
    hbm_bw: float          # bytes/s per chip
    ici_bw: float          # bytes/s per link


PEAKS: Dict[str, ChipPeaks] = {
    "TPU v5 lite": ChipPeaks(bf16_flops=197e12, hbm_bw=819e9,
                             ici_bw=1600e9 / 8 / 4),
}


def peaks(device_kind: str) -> ChipPeaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; add them "
            f"to repro.roofline.hw.PEAKS with their source") from None
