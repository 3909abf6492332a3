"""Production meshes and per-chip peaks (DESIGN.md §6).

Functions, not module constants — importing this module never touches jax
device state.
"""
from __future__ import annotations

from typing import NamedTuple

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = 1
    for s in shape:
        n *= s
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices for mesh {shape}; have {len(devices)} — run under "
            "dryrun.py, which forces XLA_FLAGS=--xla_force_host_platform_device_count=512"
        )
    import numpy as np

    return jax.sharding.Mesh(
        np.asarray(devices[:n]).reshape(shape), axes
    )


class ChipPeaks(NamedTuple):
    flops_bf16: float   # FLOP/s per chip
    hbm_bw: float       # bytes/s per chip
    hbm_bytes: int      # bytes per chip
    ici_bw: float       # bytes/s per link (1 link/axis-hop assumed)


#: published peaks keyed by `jax.Device.device_kind`. Source: Google Cloud
#: documentation, "TPU v5e" (197 TFLOP/s bf16, 819 GB/s and 16 GiB of HBM,
#: 1,600 Gbit/s of inter-chip interconnect = 4 links of 50 GB/s)
PEAKS = {
    "TPU v5 lite": ChipPeaks(
        flops_bf16=197e12, hbm_bw=819e9, hbm_bytes=16 * 1024**3, ici_bw=50e9
    ),
}

#: the chip the dry runs target (`launch/dryrun.py`'s production mesh)
DRYRUN_KIND = "TPU v5 lite"


def chip_peaks(device_kind: str) -> ChipPeaks:
    """Peaks of one chip of ``device_kind``; an unknown kind is an error."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r}; "
            f"known: {sorted(PEAKS)}"
        ) from None
