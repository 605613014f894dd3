"""Published peaks of one chip, keyed by JAX's ``device_kind``.

Source: Google Cloud documentation, "TPU v5e": 197 TFLOP/s in bf16,
16 GB of HBM at 819 GB/s, 1,600 Gbit/s of chip-to-chip interconnect.
The table is the benchmark's own copy, so that the yardstick does not
move when the program's table changes. A device that is not in it is
an error, never a default.
"""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class ChipPeaks:
    flops: float          # FLOP/s (bf16, the chip's highest float rate)
    hbm_bw: float         # HBM bytes/s
    ici_bw: float         # chip-to-chip bytes/s, all links together


PEAKS = {
    "TPU v5 lite": ChipPeaks(flops=197e12, hbm_bw=819e9,
                             ici_bw=1600e9 / 8),
}


def chip_peaks(device_kind: str) -> ChipPeaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None
