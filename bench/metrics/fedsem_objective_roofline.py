"""Kernel: the objective kernel's share of its roofline over the traced
window: the least time the chip could take for the kernel's calls (the
larger of counted operations over peak FLOP/s and counted bytes over peak
bytes/s, per call) over the kernel's device time."""


def read(run):
    return None if run.trace is None else run.trace.kernel_roofline_pct
