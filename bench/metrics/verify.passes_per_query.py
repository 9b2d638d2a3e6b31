"""Fused device verification passes (``VerifyEngine.stats['calls']``,
counted over the window) per answered request."""
from bench.readings import answered


def read(win):
    n = len(answered(win))
    return win.engine["calls"] / n if n else None
