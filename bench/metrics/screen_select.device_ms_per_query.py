"""Device milliseconds of the fused verification passes (``_fused_screen``
and ``_fused_screen_full`` module events in the trace) per answered
request."""
from bench.readings import answered


def read(win):
    n = len(answered(win))
    if win.trace is None or not n:
        return None
    launches = win.trace["launches"]["_fused_screen"]
    if not launches:
        return None
    return sum(launches) * 1e3 / n
