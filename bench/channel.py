"""The channel law of the benchmark's deployments, drawn for all requests at once.

This is a copy of the law in the program's `scenarios/iid_rayleigh.py` (paper
Table I: devices uniform in a disc, path loss 128.1 + 37.6 log10(d km) dB,
log-normal shadowing, Rayleigh fading per subcarrier, CPU cycles per sample
uniform), kept here so that a change to the program cannot move the
yardstick. It draws with numpy from one `numpy.random.Generator`,
vectorised over requests, so set-up stays short.
"""
from __future__ import annotations

import numpy as np


def large_scale_db(rng: np.random.Generator, shape, law: dict) -> np.ndarray:
    """Path loss plus shadowing in dB for devices of ``shape``."""
    u = rng.uniform(1e-3, 1.0, shape)
    dist_km = np.sqrt(u) * law["radius_m"] / 1000.0
    pl0, slope = law["pathloss_db"]
    return pl0 + slope * np.log10(dist_km) + law["shadowing_db"] * rng.standard_normal(shape)


def cycles(rng: np.random.Generator, shape, law: dict) -> np.ndarray:
    lo, hi = law["c_range"]
    return rng.uniform(lo, hi, shape).astype(np.float32)


def iid(rng: np.random.Generator, n: int, N: int, K: int, law: dict):
    """``n`` independent requests: gains (n, N, K) and cycles (n, N)."""
    loss_db = large_scale_db(rng, (n, N), law)
    fading = rng.exponential(1.0, (n, N, K))
    g = (10.0 ** (-loss_db[..., None] / 10.0) * fading).astype(np.float32)
    return g, cycles(rng, (n, N), law)
