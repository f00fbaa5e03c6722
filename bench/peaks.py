"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` JAX reports.  A device that is not in the table is an
error, never a default: a share of an unknown peak means nothing.

Source: Google Cloud documentation, "TPU v5e" (system architecture):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s per chip.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    bf16_flops: float      # FLOP/s
    hbm_bytes_per_s: float
    hbm_bytes: float


PEAKS = {
    "TPU v5 lite": Peaks(bf16_flops=197e12, hbm_bytes_per_s=819e9,
                         hbm_bytes=16e9),
}


def peaks_for(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; add it to bench/peaks.py with "
                       f"its source") from None


def roofline_seconds(flops: float, bytes_moved: float, peaks: Peaks):
    """The least time the chip could take for the work, and which bound
    sets it ("compute" or "memory")."""
    t_c = flops / peaks.bf16_flops
    t_m = bytes_moved / peaks.hbm_bytes_per_s
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
