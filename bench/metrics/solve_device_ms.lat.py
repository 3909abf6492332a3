"""Solve executable: device time of the solve and refine programs per flush,
from the trace, averaged over the cell's chips."""


def read(run):
    return None if run.trace is None else run.trace.solve_ms_per_flush
