"""Warm start: share of the window's answers that rode a cached start
(`Completion.warm_hit`), in percent, over the sends that do not overlap the
profiled stretch of a traced run. Nothing to read without the cache."""
from bench.stats import completions


def read(run):
    if run.cell.dep["serve"].get("warmstart") is None:
        return None
    done = completions(run.host)
    return 100.0 * sum(c.warm_hit for c in done) / len(done) if done else None
