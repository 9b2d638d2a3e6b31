"""Small helpers that several metric readers share."""
from __future__ import annotations

import math

import numpy as np


def latency_percentile(win, q: float):
    """The ``q``-th percentile of client latency over every request due in
    the window, by the nearest rank; a failed request counts as infinitely
    late. None when the percentile falls on a failed request."""
    lat = np.array([r.latency_ms for r in win.requests], np.float64)
    if not lat.size:
        return None
    v = float(np.percentile(lat, q, method="inverted_cdf"))
    return v if math.isfinite(v) else None


def answered(win) -> list:
    """The gateway's responses to the requests due in the window."""
    return [r.resp for r in win.requests if r.resp is not None]


def in_window(win, t0: float, t1: float) -> float:
    """Seconds of [t0, t1] (relative to the window's start) inside it."""
    return max(0.0, min(t1, win.seconds) - max(t0, 0.0))
