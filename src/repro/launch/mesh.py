"""Production meshes for the TPU v5e target, and the chip peaks.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state — jax locks the device count on first use, and
only the dry-run is allowed to fake 512 host devices.
"""
from __future__ import annotations

from dataclasses import dataclass

from repro.utils import compat


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return compat.make_mesh(shape, axes)


def data_axes(mesh) -> tuple:
    """The batch-parallel axes of a production mesh."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def make_worker_mesh(K: int):
    """1-D mesh for the CoCoA shard_map driver."""
    return compat.make_mesh((K,), ("workers",))


@dataclass(frozen=True)
class ChipPeaks:
    """Published per-chip peaks."""
    flops_bf16: float     # FLOP/s
    hbm_bw: float         # HBM bytes/s
    ici_link_bw: float    # chip-to-chip bytes/s per link


V5E = "TPU v5 lite"       # jax's device_kind of a TPU v5e chip

# Keyed by jax's ``device_kind``. Source: Google Cloud documentation,
# "TPU v5e": 197 TFLOP/s bf16, 819 GB/s of HBM bandwidth, 1,600 Gbit/s
# of chip-to-chip interconnect (four links).
CHIP_PEAKS = {
    V5E: ChipPeaks(flops_bf16=197e12, hbm_bw=819e9,
                   ici_link_bw=1600e9 / 8 / 4),
}


def chip_peaks(device_kind: str) -> ChipPeaks:
    """The peaks of one chip; a device that is not in the table is an
    error, never a default."""
    try:
        return CHIP_PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(CHIP_PEAKS)}"
                       ) from None


def kernel_roofline(flops: float, bytes_moved: float, seconds: float,
                    peaks: ChipPeaks) -> dict:
    """Achieved FLOP/s and bytes/s of one kernel cell against a chip's
    peaks — the per-kernel roofline fractions ``bench_kernels`` reports
    and ``repro.launch.roofline --kernels`` summarizes. Lives here (not
    roofline.py) so the benchmark can import it without the dry-run
    module's fake-device environment setup."""
    return {
        "achieved_gflops": flops / seconds / 1e9,
        "achieved_gbps": bytes_moved / seconds / 1e9,
        "flops_frac_of_peak": flops / seconds / peaks.flops_bf16,
        "bw_frac_of_hbm": bytes_moved / seconds / peaks.hbm_bw,
    }
