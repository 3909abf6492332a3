"""Admission: median time a caller spends in `RealClockDriver.submit`
(padding into the bucket, warm-start lookup, enqueue), harness timer, over
the sends that do not overlap the profiled stretch of a traced run."""
from bench.stats import median_ms


def read(run):
    return median_ms(run.host.admit_s)
