"""Admission, inside the program: median `Completion.prepare_s`, the
``alloc.prepare`` span of `AllocService.prepare` (padding into the bucket,
warm-start lookup) on the caller's thread, over the sends that do not
overlap the profiled stretch of a traced run. Nothing to read where the
program keeps no such field."""
from bench.stats import completions, median_ms


def read(run):
    done = completions(run.host)
    return median_ms(c.prepare_s for c in done if hasattr(c, "prepare_s"))
