"""Answers that arrived inside the window, per second of the window."""
import numpy as np


def read(run):
    win = run.window
    return float(np.sum(win.done <= win.t0 + win.seconds) / win.seconds)
