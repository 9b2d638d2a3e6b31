"""Self time of the executor's ``repro.execute.*`` spans (sources, rounds,
modelled-I/O accounting, host-tail screens; the verification spans inside
them excluded) over the traced window, per answered request."""
from bench.readings import answered
from bench.spans import durations


def read(win):
    d = durations(win, ("repro.execute.",), self_time=True)
    n = len(answered(win))
    return sum(d) * 1e3 / n if d and n else None
