"""Profiler traces: capture one window, reduce it to device numbers.

The reduction reads the profiler's XSpace through ``jax.profiler.ProfileData``
and nothing else. A device plane is one whose name starts with ``/device:``
and that has an ``XLA Ops`` line. That line holds one event per
operation that ran, named by its HLO text (which carries the operand
shapes); busy time is the union of those intervals, averaged over the
device planes. Launches of a jitted pass are the ``XLA Modules`` events
whose name holds its jit name. Idle gaps are named by the runtime's host
event that overlaps them most. The program's own spans, and the device's
idle time put down to them, come from ``bench/spans.py``.
"""
from __future__ import annotations

import glob
import os
import re
import shutil
from collections import defaultdict

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def start(log_dir: str) -> None:
    """Start the profiler with Python tracing off: only device operations
    and the runtime's own host events are recorded."""
    import jax

    shutil.rmtree(log_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(log_dir, profiler_options=opts)


def stop_and_load(log_dir: str):
    """Stop the profiler, read its XSpace and delete the files."""
    import jax
    from jax.profiler import ProfileData

    jax.profiler.stop_trace()
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise RuntimeError(f"the profiler wrote no trace under {log_dir}")
    with open(paths[0], "rb") as f:
        data = ProfileData.from_serialized_xspace(f.read())
    shutil.rmtree(log_dir, ignore_errors=True)
    return data


def _events(line) -> list[tuple]:
    return [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
            for ev in line.events]


def _union(intervals: list[tuple]) -> list[list[float]]:
    merged: list[list[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def short_op(name: str) -> str:
    """An operation's HLO name without its number, and its result type:
    ``%fusion.3 = f32[16384,256]{...} fusion(...)`` -> ``%fusion f32[16384,256]``."""
    lhs, _, rhs = name.partition(" = ")
    lhs = re.sub(r"\.\d+$", "", lhs.strip())
    m = re.match(r"\(?(\w+\[[\d,]*\])", rhs)
    return f"{lhs} {m.group(1)}" if m else lhs[:120]


def reduce(data, window_s: float, modules: tuple = (), ops: tuple = ()) -> dict:
    """Device numbers of a traced window of ``window_s`` seconds.

    Returns ``busy_s`` (union of operation intervals, mean over the device
    planes that ran operations), ``busy_s_per_device`` (each plane's, in
    the trace's order), ``window_s``, ``n_devices``, ``device_ops``
    (the ten operations, by :func:`short_op`, with most time summed over
    devices), ``idle_gaps`` (the ten longest gaps between operations on the
    first device, named by the host), ``launches``: for each name in
    ``modules``, the seconds of each module event whose name contains it,
    ``kernel_ops``: for each name in ``ops``, (full HLO text, seconds)
    of each operation event whose instruction name contains it, and the
    program's ``spans`` and ``idle_by_span`` (``bench/spans.py``)."""
    from bench import spans

    devices, host = [], []
    for plane in data.planes:
        names = {line.name for line in plane.lines}
        if plane.name.startswith("/device:") and OPS_LINE in names:
            devices.append(plane)
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                host.extend(_events(line))
    busy, per_op = [], defaultdict(float)
    launches = {k: [] for k in modules}
    kernel_ops = {k: [] for k in ops}
    first_union = None
    for plane in devices:
        lines = {line.name: _events(line) for line in plane.lines}
        for name, s, e in lines[OPS_LINE]:
            per_op[short_op(name)] += (e - s) / 1e9
            for k in ops:
                if k in name.partition(" = ")[0]:
                    kernel_ops[k].append((name, (e - s) / 1e9))
        union = _union([(s, e) for _, s, e in lines[OPS_LINE]])
        if first_union is None:
            first_union = union
        busy.append(sum(e - s for s, e in union) / 1e9)
        for name, s, e in lines.get(MODULES_LINE, []):
            for k in modules:
                if k in name:
                    launches[k].append((e - s) / 1e9)
    gaps = []
    for (_, e0), (s1, _) in zip(first_union or [], (first_union or [])[1:]):
        gaps.append((s1 - e0, e0, s1))
    gaps.sort(reverse=True)
    top_ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:10]
    program = spans.program_spans(data)
    return {
        "busy_s": sum(busy) / len(busy) if busy else 0.0,
        "busy_s_per_device": busy,
        "window_s": window_s,
        "n_devices": len(devices),
        "device_ops": [[n, s] for n, s in top_ops],
        "idle_gaps": [[_host_in(host, s, e), g / 1e9] for g, s, e in gaps[:10]],
        "launches": launches,
        "kernel_ops": kernel_ops,
        "spans": program,
        "idle_by_span": spans.idle_by_span(data, program),
    }


OWN_SPANS = ("repro.", "bench.")  # the program's and the benchmark's spans


def _host_in(host: list[tuple], s: float, e: float) -> str:
    """The runtime's host event that overlaps [s, e] most, with the share
    of the gap it covers, or 'no host event'. The program's and the
    benchmark's own spans are left out: they enclose the gap, and
    ``idle_by_span`` puts idle time down to them."""
    best, name = 0.0, None
    for n, hs, he in host:
        if n.startswith(OWN_SPANS):
            continue
        ov = min(he, e) - max(hs, s)
        if ov > best:
            best, name = ov, n
    if name is None:
        return "no host event"
    return f"{re.sub(r'\s+', ' ', name)[:100]} ({best / (e - s):.0%} of gap)"
