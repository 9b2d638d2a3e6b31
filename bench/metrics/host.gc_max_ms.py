"""Longest garbage collection (``repro.host.gc`` span) in the traced
window; None where no collection ran."""
from bench.spans import durations


def read(win):
    d = durations(win, ("repro.host.gc",))
    return max(d) * 1e3 if d else None
