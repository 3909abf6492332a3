"""Median latency over all the window's requests, due time to answer."""
from bench.stats import percentile_ms


def read(run):
    return None if run.traffic.closed else percentile_ms(run.window, 50)
