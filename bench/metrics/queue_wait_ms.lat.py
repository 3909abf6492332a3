"""Batching: median wait from admission to the request's flush
(`Completion.wait_s`, the `MicroBatcher`'s queue), over the sends that do
not overlap the profiled stretch of a traced run."""
from bench.stats import completions, median_ms


def read(run):
    return median_ms(c.wait_s for c in completions(run.host))
