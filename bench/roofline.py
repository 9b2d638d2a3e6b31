"""Operations and bytes of the screen kernel, from its shapes.

One launch of ``screen_select_pallas`` screens ``m`` (padded) queries
against ``n`` candidate rows of width ``d``: the contraction takes 2·m·n·d
operations and forming the distances 3·m·n more. The least bytes it must
move are the candidate rows in their storage type, their f32 norms, the
f32 queries, and the slate it writes (an f32 distance and an i32 row per
slot) with one f32 norm per query. The least time is the larger of
operations over the peak rate and bytes over the peak bandwidth. The f32
contraction runs at ``Precision.HIGHEST``, several bf16 passes on the MXU,
and is held here to the bf16 peak.

The trace names each kernel operation by its HLO text, which carries the
shapes: ``%screen_select_pallas.1 = (f32[32,18]..., ...) custom-call(
f32[32,256]... %q, f32[16384,256]... %x, f32[1,16384]... %xn2)``.
"""
from __future__ import annotations

import json
import re
from pathlib import Path
from typing import Optional

ITEMSIZE = {"f32": 4, "bf16": 2, "s8": 1}
_SCREEN = re.compile(
    r"= \(f32\[(\d+),(\d+)\].*?custom-call\("
    r"f32\[(\d+),(\d+)\]\S* %[\w.-]+, (f32|bf16|s8)\[(\d+),(\d+)\]")


def load_peaks(kind: str) -> dict:
    """The peaks of a device kind, from ``peaks.json``; an unknown kind is
    an error, not a default."""
    with open(Path(__file__).resolve().parent / "peaks.json") as f:
        table = json.load(f)
    if kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    return table["devices"][kind]


def screen_shapes(hlo: str) -> Optional[tuple]:
    """(m, n, d, storage type, slate) of a screen kernel operation, or
    None when the text is not one."""
    g = _SCREEN.search(hlo)
    if g is None:
        return None
    m, slate, _, d, dtype, n, _ = g.groups()
    return int(m), int(n), int(d), dtype, int(slate)


def screen_cost(m: int, n: int, d: int, dtype: str,
                slate: int) -> tuple[float, float]:
    """(operations, bytes) of one screen kernel launch."""
    flops = 2.0 * m * n * d + 3.0 * m * n
    nbytes = n * d * ITEMSIZE[dtype] + 4 * n + 4 * m * d + 8 * m * slate \
        + 4 * m
    return flops, nbytes


def least_seconds(flops: float, nbytes: float,
                  peaks: dict) -> tuple[float, str]:
    """The least time of a launch on the device and which bound sets it."""
    t_ops = flops / peaks["flops_bf16"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return (t_bytes, "bytes") if t_bytes >= t_ops else (t_ops, "operations")
