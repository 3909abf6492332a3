"""Host-clock statistics of a window, shared by the metrics' readers."""
from __future__ import annotations

import numpy as np


def latencies_ms(win) -> np.ndarray:
    """Each send's latency from its due time to its answer, in ms; a failed
    request counts as missing every limit (infinite)."""
    lat = (win.done - win.due) * 1e3
    failed = np.array([e is not None for e in win.errors], bool)
    return np.where(np.isfinite(lat) & ~failed, lat, np.inf)


def percentile_ms(win, q: float):
    lat = latencies_ms(win)
    if not len(lat):
        return None
    v = float(np.percentile(lat, q))
    return v if np.isfinite(v) else None


def completions(win) -> list:
    return [c for c in win.completions if c is not None]


def median_ms(values):
    values = list(values)
    return float(np.median(values) * 1e3) if values else None
