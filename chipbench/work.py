"""The work a CoCoA round requires, counted from the algorithm's shapes.

Nothing here looks at how the program implements a step: a gather
that reads more than the visited columns, padding, or a second pass
over a vector are the program's waste and are not counted as work.
All arrays are float32 (4 bytes).
"""
from __future__ import annotations

from chipbench.peaks import ChipPeaks


def scd_call(m: int, H: int, n_local: int) -> tuple[int, int]:
    """FLOPs and HBM bytes of H SCD steps on one worker: per step a
    length-m dot and a length-m axpy (4 m FLOPs), the visited column
    streamed in (4 m bytes), four per-step scalars; the alpha block in
    and out, w in and the local residual out."""
    flops = 4 * m * H
    nbytes = 4 * H * m + 16 * H + 8 * n_local + 8 * m
    return flops, nbytes


def round_work(m: int, H: int, n_local: int, K: int) -> tuple[int, int]:
    """FLOPs and HBM bytes of one whole round over K workers: every
    worker's SCD steps, then the exchange and apply of the m-vector
    update (read K updates and w, write w: (K + 2) m words; K m adds)
    and the primal metric (read w once more, 2 m FLOPs)."""
    f, b = scd_call(m, H, n_local)
    flops = K * f + K * m + 2 * m
    nbytes = K * b + (K + 3) * 4 * m
    return flops, nbytes


def allreduce_ici_bytes(m: int, K: int) -> float:
    """Bytes each chip sends for a ring all-reduce of an m-vector of
    float32 over K chips: 2 (K - 1) / K of the vector."""
    return 2 * (K - 1) / K * 4 * m


def roofline_seconds(flops: float, nbytes: float, peaks: ChipPeaks,
                     chips: int = 1, ici_bytes: float = 0.0) -> float:
    """The least time the chips could take: the larger of the FLOP,
    HBM and interconnect bounds, with the work spread over ``chips``."""
    return max(flops / (chips * peaks.flops),
               nbytes / (chips * peaks.hbm_bw),
               ici_bytes / peaks.ici_bw)
