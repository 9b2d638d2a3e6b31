"""Self time of the verification engine's host work, the
``repro.verify.launch``, ``.rerank``, ``.certify`` and ``.fallback`` spans,
over the traced window, per answered request."""
from bench.readings import answered
from bench.spans import durations

NAMES = ("repro.verify.launch", "repro.verify.rerank", "repro.verify.certify",
         "repro.verify.fallback")


def read(win):
    d = durations(win, NAMES, self_time=True)
    n = len(answered(win))
    return sum(d) * 1e3 / n if d and n else None
