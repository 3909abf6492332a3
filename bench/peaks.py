"""Published peaks of one chip, keyed by `jax.Device.device_kind`.

A copy of the program's `launch/mesh.PEAKS`, kept with the benchmark so that
a change to the program cannot move the yardstick. Source: Google Cloud
documentation, "TPU v5e": 197 TFLOP/s in bf16, 16 GiB of HBM at 819 GB/s,
1,600 Gbit/s of inter-chip interconnect.
"""
from __future__ import annotations

from typing import NamedTuple


class ChipPeaks(NamedTuple):
    flops: float        # FLOP/s per chip (bf16, the chip's published peak)
    hbm_bw: float       # bytes/s per chip
    hbm_bytes: int      # bytes per chip


PEAKS = {
    "TPU v5 lite": ChipPeaks(flops=197e12, hbm_bw=819e9, hbm_bytes=16 * 1024**3),
}


def peaks(device_kind: str) -> ChipPeaks:
    """Peaks of one chip of ``device_kind``; an unknown kind is an error."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind {device_kind!r}; "
                         f"known: {sorted(PEAKS)}") from None
