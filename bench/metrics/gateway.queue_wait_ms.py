"""Median of the gateway's own admission-to-dispatch wait
(``Response.queue_wait_ms``) over the window's answers."""
import numpy as np

from bench.readings import answered


def read(win):
    waits = [r.queue_wait_ms for r in answered(win)]
    return float(np.median(waits)) if waits else None
