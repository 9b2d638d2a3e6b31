"""Program spans on the JAX profiler's own host trace.

``span(name, **meta)`` marks where a layer's work happens. Names read
``repro.<layer>.<what>``; the metadata rides on the event. The spans land
in the profiler's XSpace beside the device operations, on one clock, so a
trace reduction can put each device idle gap down to the layer the host
was in. There is no buffer or exporter of the program's own: a span is
recorded only while ``jax.profiler`` traces.

With the profiler off, or without ``jax`` loaded (then no profiler can
run, and the numpy-only host path never imports ``jax``), ``span`` hands
back a shared no-op, at well under a microsecond.
"""
from __future__ import annotations

import gc
import sys


class _Off:
    """The shared span while nothing traces: records nothing."""

    def __enter__(self):
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def set_metadata(self, **meta) -> None:
        pass


_OFF = _Off()


def span(name: str, **meta):
    """A context manager recording ``name`` with ``meta`` while the JAX
    profiler traces this process. Never call it inside a jitted function:
    it would record the trace, not the calls."""
    jax = sys.modules.get("jax")
    if jax is None or not jax.profiler.TraceAnnotation.is_enabled():
        return _OFF
    return jax.profiler.TraceAnnotation(name, **meta)


# the collection in progress: start and stop run on the collecting thread,
# and the interpreter never runs two collections at once
_GC_OPEN: list = []


def _gc_span(phase: str, info: dict) -> None:
    if phase == "start":
        s = span("repro.host.gc", gen=info["generation"])
        if s is not _OFF:
            s.__enter__()
            _GC_OPEN.append(s)
    elif _GC_OPEN:
        _GC_OPEN.pop().__exit__(None, None, None)


def install_gc_spans() -> None:
    """Record each garbage collection as a ``repro.host.gc`` span while
    the profiler traces. Idempotent: the hook is process-wide."""
    if _gc_span not in gc.callbacks:
        gc.callbacks.append(_gc_span)
