"""Median duration of the ``repro.gateway.batch`` spans in the traced
window: how long the dispatcher takes to serve one formed batch."""
import numpy as np

from bench.spans import durations


def read(win):
    d = durations(win, ("repro.gateway.batch",))
    return float(np.median(d)) * 1e3 if d else None
