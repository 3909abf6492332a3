"""Flush host work: median over the window's flushes of `FlushTiming.host_s`,
the ``alloc.flush`` span's wall time less its solve (stacking, executable
lookup and placement, scoring, unpadding, recording), each flush counted
once however many answers it holds; over the sends that do not overlap the
profiled stretch of a traced run. Nothing to read where the program keeps no
such record."""
from bench.stats import completions, median_ms


def read(run):
    flushes = {}
    for c in completions(run.host):
        f = getattr(c, "flush", None)
        if f is not None:
            flushes[f.flush_id] = f.host_s
    return median_ms(flushes.values())
