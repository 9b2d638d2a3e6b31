"""Program spans in a profiler trace: the host side of each layer.

The program marks each layer's host work with a ``repro.<layer>.<what>``
event on the thread that did it (``repro.obs.span``). They sit in the same
XSpace as the device operations, on one clock. This module reads them out
and puts the device's idle time down to the layer the host was in:

- :func:`program_spans`: every ``repro.*`` host event, with its thread
  line, start, end, metadata and self time (its duration less the part
  its child spans on that line cover);
- :func:`idle_by_span`: the first device's idle time, each instant put
  down to the innermost span then open on the dispatcher thread (the line
  that holds ``repro.gateway.batch``), ``none`` where none was; and the
  idle time that a collection or ingest work overlapped on any thread.

Times are nanoseconds on the trace's clock; idle times are seconds.
"""
from __future__ import annotations

from bisect import bisect_right
from collections import defaultdict

from bench.trace import OPS_LINE, _events, _union

PREFIX = "repro."
DISPATCH = "repro.gateway.batch"  # the span that marks the dispatcher's line
# spans whose overlap with idle time is reported on any thread
ANY_THREAD = {"repro.host.gc": ("repro.host.gc",),
              "repro.ingest.*": ("repro.ingest.",)}


def program_spans(data) -> list[dict]:
    """Each ``repro.*`` event of the host planes: ``name``, ``line`` (the
    plane and thread line), ``start``, ``end``, ``meta`` and ``self``."""
    spans = []
    for plane in data.planes:
        if not plane.name.startswith("/host:CPU"):
            continue
        for line in plane.lines:
            evs = [{"name": ev.name, "line": f"{plane.name}/{line.name}",
                    "start": ev.start_ns, "end": ev.start_ns + ev.duration_ns,
                    "meta": dict(ev.stats)}
                   for ev in line.events if ev.name.startswith(PREFIX)]
            _set_self_times(evs)
            spans.extend(evs)
    return spans


def _set_self_times(evs: list[dict]) -> None:
    """Self time of each span of one thread line, where spans nest."""
    evs.sort(key=lambda s: (s["start"], -s["end"]))
    stack: list[dict] = []
    for s in evs:
        s["self"] = s["end"] - s["start"]
        while stack and stack[-1]["end"] <= s["start"]:
            stack.pop()
        if stack:  # the parent loses what this child covers
            parent = stack[-1]
            parent["self"] -= min(s["end"], parent["end"]) - s["start"]
        stack.append(s)


def _innermost(spans: list[dict]) -> tuple[list, list]:
    """The line's timeline as consecutive segments: (starts, [(start, end,
    name of the innermost open span)]), gaps between spans left out."""
    segs = []
    stack: list[tuple] = []  # (end, name)
    t = None

    def close_until(limit):
        nonlocal t
        while stack and stack[-1][0] <= limit:
            end, name = stack.pop()
            if t < end:
                segs.append((t, end, name))
            t = max(t, end)

    for s in sorted(spans, key=lambda s: (s["start"], -s["end"])):
        if stack:
            close_until(s["start"])
        if stack and t < s["start"]:
            segs.append((t, s["start"], stack[-1][1]))
        t = s["start"] if t is None else max(t, s["start"])
        end = min(s["end"], stack[-1][0]) if stack else s["end"]
        stack.append((end, s["name"]))
    if stack:
        close_until(float("inf"))
    return [a for a, _, _ in segs], segs


def _overlap(starts: list, segs: list, a: float, b: float, out: dict) -> float:
    """Add each segment's overlap with [a, b] to ``out`` by name; returns
    the time covered."""
    covered = 0.0
    i = max(0, bisect_right(starts, a) - 1)
    while i < len(segs) and segs[i][0] < b:
        s, e, name = segs[i]
        ov = min(e, b) - max(s, a)
        if ov > 0:
            out[name] += ov
            covered += ov
        i += 1
    return covered


def device_idle(data) -> list[tuple]:
    """The first device's idle intervals: the gaps between its operations
    and the stretches before the first and after the last, within the
    trace's extent over every plane."""
    first, lo, hi = None, float("inf"), float("-inf")
    for plane in data.planes:
        for line in plane.lines:
            for _, s, e in _events(line):
                lo, hi = min(lo, s), max(hi, e)
        if first is None and plane.name.startswith("/device:") and \
                OPS_LINE in {line.name for line in plane.lines}:
            first = _union([(s, e) for line in plane.lines
                            if line.name == OPS_LINE
                            for _, s, e in _events(line)])
    if not first:
        return []
    bounds = [lo] + [x for s, e in first for x in (s, e)] + [hi]
    return [(a, b) for a, b in zip(bounds[::2], bounds[1::2]) if b > a]


def idle_by_span(data, spans: list[dict]) -> dict:
    """``idle_s``: the first device's idle seconds in the trace;
    ``dispatcher``: those seconds by the innermost span open on the
    dispatcher's line (``none`` where none was); ``any_thread``: the idle
    seconds that each of :data:`ANY_THREAD` overlapped on any line."""
    idle = device_idle(data)
    lines = {s["line"] for s in spans if s["name"] == DISPATCH}
    on_line = [s for s in spans if s["line"] in lines]
    starts, segs = _innermost(on_line)
    by = defaultdict(float)
    for a, b in idle:
        by["none"] += (b - a) - _overlap(starts, segs, a, b, by)
    any_thread = {}
    for key, prefixes in ANY_THREAD.items():
        union = [(s, e, key) for s, e in _union(
            [(s["start"], s["end"]) for s in spans
             if s["name"].startswith(prefixes)])]
        u_starts = [s for s, _, _ in union]
        hit = defaultdict(float)
        for a, b in idle:
            _overlap(u_starts, union, a, b, hit)
        any_thread[key] = hit[key] / 1e9
    return {"idle_s": sum(b - a for a, b in idle) / 1e9,
            "dispatcher": {k: v / 1e9 for k, v in
                           sorted(by.items(), key=lambda kv: -kv[1])},
            "any_thread": any_thread}


def durations(win, names: tuple, self_time: bool = False):
    """Seconds of each span of the traced window whose name starts with
    one of ``names`` (its self time if ``self_time``); None where the
    window holds no such span, as with a program that records none."""
    spans = [] if win.trace is None else win.trace.get("spans") or []
    key = "self" if self_time else None
    d = [(s[key] if key else s["end"] - s["start"]) / 1e9
         for s in spans if s["name"].startswith(names)]
    return d or None
