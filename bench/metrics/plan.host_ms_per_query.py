"""Host milliseconds in ``repro.plan`` spans (building each batch's query
plan) over the traced window, per answered request."""
from bench.readings import answered
from bench.spans import durations


def read(win):
    d, n = durations(win, ("repro.plan",)), len(answered(win))
    return sum(d) * 1e3 / n if d and n else None
