"""Batching: median `Completion.inbox_s`, the part of a request's wait
(`queue_wait_ms.lat`) it spent in the driver's inbox before the solver
thread, busy with earlier flushes, admitted it; over the sends that do not
overlap the profiled stretch of a traced run. Nothing to read where the
program keeps no such field."""
from bench.stats import completions, median_ms


def read(run):
    done = completions(run.host)
    return median_ms(c.inbox_s for c in done if hasattr(c, "inbox_s"))
