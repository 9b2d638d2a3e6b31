"""jit'd public wrappers around the Pallas kernels.

Handles shape padding to block multiples, breakpoint tables, and backend
dispatch: on TPU the kernels run compiled; on any other backend they run
in interpret mode, executing the same kernel bodies in Python (how the
test suite validates them on CPU).
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from ..core.summarization import SummarizationConfig, breakpoints
from .ed_scan_kernel import (
    min_ed_pallas,
    screen_select_pallas,
    screen_select_quant_pallas,
    topk_ed_pallas,
)
from .lb_kernel import mindist_pallas
from .paa_kernel import paa_pallas
from .sax_pack_kernel import sax_pack_pallas

# compiled on TPU, interpret mode everywhere else
INTERPRET = jax.default_backend() != "tpu"

# sentinel |x|^2 for pad candidates: dominates any real screened distance
# without overflowing the f32 d2 arithmetic (see screen_select)
BIG_NORM2 = 1e30


def _pad_rows(x: jnp.ndarray, mult: int, fill=0.0) -> tuple[jnp.ndarray, int]:
    b = x.shape[0]
    pad = (-b) % mult
    if pad:
        x = jnp.concatenate(
            [x, jnp.full((pad,) + x.shape[1:], fill, x.dtype)], axis=0
        )
    return x, b


def paa(x: jnp.ndarray, cfg: SummarizationConfig, *, block_b: int = 256) -> jnp.ndarray:
    """(B, n) -> (B, w) PAA summaries via the Pallas kernel."""
    x = jnp.asarray(x, jnp.float32)
    if x.shape[0] == 0:  # empty batch: no kernel launch, no row padding
        return jnp.zeros((0, cfg.n_segments), jnp.float32)
    block_b = min(block_b, max(8, x.shape[0]))
    xp, b = _pad_rows(x, block_b)
    out = paa_pallas(xp, cfg.n_segments, block_b=block_b, interpret=INTERPRET)
    return out[:b]


def sax_and_keys(
    p: jnp.ndarray, cfg: SummarizationConfig, *, block_b: int = 256
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """PAA (B, w) -> (symbols (B, w) int32, sortable keys (B, nw) uint32)."""
    p = jnp.asarray(p, jnp.float32)
    if p.shape[0] == 0:  # empty batch: no kernel launch, no row padding
        return (
            jnp.zeros((0, cfg.n_segments), jnp.int32),
            jnp.zeros((0, cfg.key_words), jnp.uint32),
        )
    block_b = min(block_b, max(8, p.shape[0]))
    pp, b = _pad_rows(p, block_b)
    bps = jnp.asarray(breakpoints(cfg.card_bits))
    sym, keys = sax_pack_pallas(
        pp, bps, cfg.card_bits, n_words=cfg.key_words, block_b=block_b,
        interpret=INTERPRET,
    )
    return sym[:b], keys[:b]


def summarize(
    x: jnp.ndarray, cfg: SummarizationConfig
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Full device ingest front: series -> (paa, symbols, sortable keys)."""
    p = paa(x, cfg)
    sym, keys = sax_and_keys(p, cfg)
    return p, sym, keys


def min_ed(
    q: jnp.ndarray,
    x: jnp.ndarray,
    *,
    block_m: int = 128,
    block_n: int = 512,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Per-query (min squared ED, argmin) over candidate series.

    q: (m, d), x: (n, d). Pads m/n with sentinels; d to a lane multiple."""
    q = jnp.asarray(q, jnp.float32)
    x = jnp.asarray(x, jnp.float32)
    m, d = q.shape
    n = x.shape[0]
    if m == 0:  # empty query batch
        return jnp.zeros((0,), jnp.float32), jnp.zeros((0,), jnp.int32)
    if n == 0:  # no candidates: nothing to win the min
        return jnp.full((m,), jnp.inf, jnp.float32), jnp.full((m,), -1, jnp.int32)
    block_m = min(block_m, max(8, m))
    block_n = min(block_n, max(8, n))
    dp = (-d) % 128
    if dp:  # zero-pad the contraction dim: adds 0 to every distance
        q = jnp.concatenate([q, jnp.zeros((m, dp), q.dtype)], axis=1)
        x = jnp.concatenate([x, jnp.zeros((n, dp), x.dtype)], axis=1)
    qp, _ = _pad_rows(q, block_m)
    # pad candidates with +large rows so they never win the min
    xp, _ = _pad_rows(x, block_n, fill=1e15)
    md, am = min_ed_pallas(qp, xp, block_m=block_m, block_n=block_n, interpret=INTERPRET)
    return md[:m], am[:m]


def topk_ed(
    q: jnp.ndarray,
    x: jnp.ndarray,
    k: int,
    *,
    block_m: int = 128,
    block_n: int = 512,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Per-query k smallest squared EDs + candidate rows, ascending.

    q: (m, d), x: (n, d) -> ((m, k) f32, (m, k) int32). Pads m/n/d to block
    multiples (candidate pads get a +large sentinel fill) and always returns
    k columns: when n < k the tail is (inf, -1). Ties break toward the
    smaller candidate index (the kernel's lexicographic semantics)."""
    q = jnp.asarray(q, jnp.float32)
    x = jnp.asarray(x, jnp.float32)
    m, d = q.shape
    n = x.shape[0]
    if m == 0:  # empty query batch
        return jnp.zeros((0, k), jnp.float32), jnp.zeros((0, k), jnp.int32)
    if n == 0:  # no candidates: every requested slot is explicit padding
        return (
            jnp.full((m, k), jnp.inf, jnp.float32),
            jnp.full((m, k), -1, jnp.int32),
        )
    kk = max(1, min(k, n))
    block_m = min(block_m, max(8, m))
    block_n = min(block_n, max(8, n))
    dp = (-d) % 128
    if dp:  # zero-pad the contraction dim: adds 0 to every distance
        q = jnp.concatenate([q, jnp.zeros((m, dp), q.dtype)], axis=1)
        x = jnp.concatenate([x, jnp.zeros((n, dp), x.dtype)], axis=1)
    qp, _ = _pad_rows(q, block_m)
    # pad candidates with +large rows; they only surface when n < kk + pad,
    # and are mapped to (inf, -1) below via their out-of-range index
    xp, _ = _pad_rows(x, block_n, fill=1e15)
    vals, idxs = topk_ed_pallas(
        qp, xp, kk, block_m=block_m, block_n=block_n, interpret=INTERPRET
    )
    vals, idxs = vals[:m], idxs[:m]
    invalid = idxs >= n  # row-pad candidates and never-filled (inf) slots
    vals = jnp.where(invalid, jnp.inf, vals)
    idxs = jnp.where(invalid, -1, idxs)
    if kk < k:  # fewer candidates than requested neighbors
        fill_v = jnp.full((m, k - kk), jnp.inf, vals.dtype)
        fill_i = jnp.full((m, k - kk), -1, idxs.dtype)
        vals = jnp.concatenate([vals, fill_v], axis=1)
        idxs = jnp.concatenate([idxs, fill_i], axis=1)
    return vals, idxs


def candidate_bucket(e: int, min_bucket: int = 64) -> int:
    """The power-of-two candidate bucket (min ``min_bucket``) ``e`` pads to
    — the shared shape discipline of every bucketed launcher, so steady
    state serving hits a handful of cached traces."""
    return 1 << max(min_bucket.bit_length() - 1, (max(1, e) - 1).bit_length())


def topk_ed_bucketed(
    q: jnp.ndarray, x: jnp.ndarray, k: int
) -> tuple[np.ndarray, np.ndarray]:
    """``topk_ed`` with the candidate count padded up to a power-of-two
    bucket (min 64) so jit sees a handful of stable shapes across serving
    passes — the launcher used by the shared query executor.

    Bucket-padding rows carry a +large sentinel; any that surface (only
    possible when the true candidate count < k) are mapped to (inf, -1),
    so results are indistinguishable from an unpadded launch. When the
    caller already padded to the bucket (``e == bucket``) the table is
    passed through without the concat copy — the fast path arenas rely on.
    Returns host ((m, kk) f32 d2, (m, kk) int64 rows into ``x``), kk =
    min(k, |x|)."""
    m = np.asarray(q).shape[0]
    e = x.shape[0]
    if e == 0:  # no candidates: every requested slot is explicit padding
        return (
            np.full((m, k), np.inf, np.float32),
            np.full((m, k), -1, np.int64),
        )
    x = jnp.asarray(x, jnp.float32)
    bucket = candidate_bucket(e)
    if bucket != e:  # fast path: already bucket-sized tables skip the copy
        pad = jnp.full((bucket - e, x.shape[1]), 1e15, jnp.float32)
        x = jnp.concatenate([x, pad])
    v, i = topk_ed(q, x, min(k, e))
    i = np.asarray(i).astype(np.int64)
    v = np.asarray(v)
    invalid = (i < 0) | (i >= e)  # bucket padding / never-filled slots
    return np.where(invalid, np.inf, v), np.where(invalid, -1, i)


def screen_select(
    q: jnp.ndarray,
    x: jnp.ndarray,
    xn2: jnp.ndarray,
    k: int,
    *,
    block_m: int = 128,
    block_n: int = 512,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One fused verification launch: f32 matmul-form screen over candidates
    with PRECOMPUTED squared norms, in-kernel top-k slate selection, and the
    per-query |q|^2 certificate term.

    q: (m, d), x: (n, d), xn2: (n,) -> ((m, k) f32 d2 ascending, (m, k)
    int32 rows, (m,) f32 |q|^2). Pads m/n/d to block multiples; candidate
    pads get zero rows with a :data:`BIG_NORM2` sentinel norm (the screen
    uses ``xn2``, not the rows, for the |x|^2 term, so the sentinel keeps
    pads out of every slate without f32 overflow) and surface as (inf, -1).
    Ties break toward the smaller candidate index (lexicographic (d2, index)
    — the ``screen_select_ref`` oracle semantics).

    ``x`` may arrive in bf16 (a quantized arena): the storage dtype is
    preserved through padding — halving the kernel's HBM traffic — and the
    kernel body upcasts each tile to f32 in-register, so compute precision
    is unchanged. Anything else is cast to f32 up front."""
    q = jnp.asarray(q, jnp.float32)
    x = jnp.asarray(x)
    if x.dtype != jnp.bfloat16:
        x = x.astype(jnp.float32)
    xn2 = jnp.asarray(xn2, jnp.float32)
    m, d = q.shape
    n = x.shape[0]
    if m == 0:  # empty query batch
        return (
            jnp.zeros((0, k), jnp.float32),
            jnp.zeros((0, k), jnp.int32),
            jnp.zeros((0,), jnp.float32),
        )
    if n == 0:  # no candidates: every requested slot is explicit padding
        return (
            jnp.full((m, k), jnp.inf, jnp.float32),
            jnp.full((m, k), -1, jnp.int32),
            jnp.sum(q * q, axis=-1),
        )
    kk = max(1, min(k, n))
    block_m = min(block_m, max(8, m))
    block_n = min(block_n, max(8, n))
    dp = (-d) % 128
    if dp:  # zero-pad the contraction dim: adds 0 to every distance
        q = jnp.concatenate([q, jnp.zeros((m, dp), q.dtype)], axis=1)
        x = jnp.concatenate([x, jnp.zeros((n, dp), x.dtype)], axis=1)
    qp, _ = _pad_rows(q, block_m)
    xp, _ = _pad_rows(x, block_n)  # zero rows; the sentinel lives in xn2
    xn2p, _ = _pad_rows(xn2, block_n, fill=BIG_NORM2)
    vals, idxs, qn2 = screen_select_pallas(
        qp, xp, xn2p, kk, block_m=block_m, block_n=block_n, interpret=INTERPRET
    )
    vals, idxs, qn2 = vals[:m], idxs[:m], qn2[:m]
    invalid = idxs >= n  # row-pad candidates and never-filled (inf) slots
    vals = jnp.where(invalid, jnp.inf, vals)
    idxs = jnp.where(invalid, -1, idxs)
    if kk < k:  # fewer candidates than requested slate slots
        vals = jnp.concatenate(
            [vals, jnp.full((m, k - kk), jnp.inf, vals.dtype)], axis=1)
        idxs = jnp.concatenate(
            [idxs, jnp.full((m, k - kk), -1, idxs.dtype)], axis=1)
    return vals, idxs, qn2


def screen_select_quant(
    q: jnp.ndarray,
    x: jnp.ndarray,
    scale: jnp.ndarray,
    xn2: jnp.ndarray,
    k: int,
    *,
    block_m: int = 128,
    block_n: int = 512,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """:func:`screen_select` over an int8 arena with per-row f32 scales.

    q: (m, d) f32, x: (n, d) int8, scale: (n,) f32, xn2: (n,) f32 — the
    squared norms of the DEQUANTIZED rows, so the screen is self-consistent
    with the stored values. The kernel upcasts each int8 tile to f32
    in-register and applies the scale after the MXU contraction (the cross
    term ``<q, s*v> = s * <q, v>``), quartering HBM/h2d traffic while
    keeping compute in f32. Same padding, sentinel, and tie semantics as
    :func:`screen_select`; pad rows get scale 1 (their sentinel lives in
    ``xn2``)."""
    q = jnp.asarray(q, jnp.float32)
    x = jnp.asarray(x, jnp.int8)
    scale = jnp.asarray(scale, jnp.float32)
    xn2 = jnp.asarray(xn2, jnp.float32)
    m, d = q.shape
    n = x.shape[0]
    if m == 0:  # empty query batch
        return (
            jnp.zeros((0, k), jnp.float32),
            jnp.zeros((0, k), jnp.int32),
            jnp.zeros((0,), jnp.float32),
        )
    if n == 0:  # no candidates: every requested slot is explicit padding
        return (
            jnp.full((m, k), jnp.inf, jnp.float32),
            jnp.full((m, k), -1, jnp.int32),
            jnp.sum(q * q, axis=-1),
        )
    kk = max(1, min(k, n))
    block_m = min(block_m, max(8, m))
    block_n = min(block_n, max(8, n))
    dp = (-d) % 128
    if dp:  # zero-pad the contraction dim: adds 0 to every distance
        q = jnp.concatenate([q, jnp.zeros((m, dp), q.dtype)], axis=1)
        x = jnp.concatenate([x, jnp.zeros((n, dp), x.dtype)], axis=1)
    qp, _ = _pad_rows(q, block_m)
    xp, _ = _pad_rows(x, block_n)  # zero rows; the sentinel lives in xn2
    sp, _ = _pad_rows(scale, block_n, fill=1.0)
    xn2p, _ = _pad_rows(xn2, block_n, fill=BIG_NORM2)
    vals, idxs, qn2 = screen_select_quant_pallas(
        qp, xp, sp, xn2p, kk, block_m=block_m, block_n=block_n,
        interpret=INTERPRET
    )
    vals, idxs, qn2 = vals[:m], idxs[:m], qn2[:m]
    invalid = idxs >= n  # row-pad candidates and never-filled (inf) slots
    vals = jnp.where(invalid, jnp.inf, vals)
    idxs = jnp.where(invalid, -1, idxs)
    if kk < k:  # fewer candidates than requested slate slots
        vals = jnp.concatenate(
            [vals, jnp.full((m, k - kk), jnp.inf, vals.dtype)], axis=1)
        idxs = jnp.concatenate(
            [idxs, jnp.full((m, k - kk), -1, idxs.dtype)], axis=1)
    return vals, idxs, qn2


def mindist(
    q_paa: jnp.ndarray,
    lo: jnp.ndarray,
    hi: jnp.ndarray,
    cfg: SummarizationConfig,
    *,
    block_b: int = 1024,
) -> jnp.ndarray:
    """Blocked MINDIST_PAA_SAX lower bounds. lo/hi: (B, w) -> (B,) f32."""
    lo = jnp.asarray(lo, jnp.float32)
    hi = jnp.asarray(hi, jnp.float32)
    b = lo.shape[0]
    if b == 0:  # empty batch: no kernel launch, no row padding
        return jnp.zeros((0,), jnp.float32)
    block_b = min(block_b, max(8, b))
    lop, _ = _pad_rows(lo, block_b, fill=0.0)
    hip, _ = _pad_rows(hi, block_b, fill=0.0)
    out = mindist_pallas(
        jnp.asarray(q_paa, jnp.float32), lop, hip, cfg.segment_len,
        block_b=block_b, interpret=INTERPRET,
    )
    return out[:b]
