"""Host milliseconds spent waiting for the device's slates
(``repro.verify.wait``: the downloads that block on each pass) over the
traced window, per answered request."""
from bench.readings import answered
from bench.spans import durations


def read(win):
    d, n = durations(win, ("repro.verify.wait",)), len(answered(win))
    return sum(d) * 1e3 / n if d and n else None
