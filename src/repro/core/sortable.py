"""Sortable summarizations — the paper's core contribution.

Plain SAX words sort by segment 0 first, so sorting scatters series that are
similar overall but differ in their first segment. Interleaving the bits of
all segments MSB-first produces a z-order key: lexicographic order on the
interleaved key keeps series that are similar in *all* segments adjacent.

Keys are fixed-width bit strings of w*c bits packed big-endian into uint32
words (TPU-friendly: no 64-bit integer ops needed; multi-word keys sort
lexicographically with ``lax.sort(num_keys=n_words)`` or ``np.lexsort``).
"""
from __future__ import annotations

import numpy as np

from .summarization import SummarizationConfig, array_module


def _bit_positions(cfg: SummarizationConfig) -> np.ndarray:
    """Key bit index (0 = MSB of the key) for (bit b of symbol, segment s).

    Interleaved layout: key bit p = b * w + s, i.e. the MSBs of all segments
    come first (segment order), then the second bits, etc.
    """
    w, c = cfg.n_segments, cfg.card_bits
    b = np.arange(c)[:, None]  # bit index within symbol, 0 = MSB
    s = np.arange(w)[None, :]
    return (b * w + s).reshape(-1)  # (c*w,) in (b-major, s-minor) order


def interleave(sym, cfg: SummarizationConfig):
    """Bit-interleave SAX symbols into sortable keys.

    sym: (..., w) int32 symbols in [0, 2**c)
    returns: (..., n_words) uint32 key words, word 0 most significant,
             bit 31 of each word most significant. Unused low bits are 0.
    """
    xp = array_module(sym)
    w, c = cfg.n_segments, cfg.card_bits
    nw = cfg.key_words
    # bits of each symbol, MSB first: (..., c, w)
    shifts = xp.arange(c - 1, -1, -1, dtype=sym.dtype)
    bits = (sym[..., None, :] >> shifts[:, None]) & 1  # (..., c, w)
    flat = bits.reshape(sym.shape[:-1] + (c * w,))  # already p = b*w + s order
    # pad to nw*32 bits
    pad = nw * 32 - c * w
    if pad:
        zeros = xp.zeros(sym.shape[:-1] + (pad,), dtype=flat.dtype)
        flat = xp.concatenate([flat, zeros], axis=-1)
    words = flat.reshape(sym.shape[:-1] + (nw, 32))
    weights = (xp.uint32(1) << xp.arange(31, -1, -1, dtype=xp.uint32))
    return (words.astype(xp.uint32) * weights).sum(axis=-1).astype(xp.uint32)


def deinterleave(keys, cfg: SummarizationConfig):
    """Inverse of :func:`interleave`. keys: (..., n_words) uint32 -> (..., w) int32."""
    xp = array_module(keys)
    w, c = cfg.n_segments, cfg.card_bits
    nw = cfg.key_words
    shifts = xp.arange(31, -1, -1, dtype=xp.uint32)
    bits = (keys[..., :, None] >> shifts) & xp.uint32(1)  # (..., nw, 32)
    flat = bits.reshape(keys.shape[:-1] + (nw * 32,))[..., : c * w]
    bw = flat.reshape(keys.shape[:-1] + (c, w)).astype(xp.int32)
    weights = (1 << xp.arange(c - 1, -1, -1)).astype(xp.int32)
    return (bw * weights[:, None]).sum(axis=-2)


def pack_u64(keys: np.ndarray) -> np.ndarray:
    """Pack (N, n_words) uint32 key words into (N, ceil(n_words/2)) uint64
    columns (big-endian order preserved): lexicographic order is unchanged
    but host sorts compare half as many columns (~2x faster; §Perf)."""
    n, nw = keys.shape
    if nw % 2:
        keys = np.concatenate([keys, np.zeros((n, 1), np.uint32)], axis=1)
        nw += 1
    k64 = keys.astype(np.uint64)
    return (k64[:, 0::2] << np.uint64(32)) | k64[:, 1::2]


def lexsort_keys(keys: np.ndarray) -> np.ndarray:
    """Stable ascending argsort of multi-word keys via the packed-u64 path."""
    packed = pack_u64(keys)
    if packed.shape[1] == 1:
        return np.argsort(packed[:, 0], kind="stable")
    return np.lexsort(tuple(packed[:, i] for i in range(packed.shape[1] - 1, -1, -1)))


def sort_by_keys(keys: np.ndarray, *payloads: np.ndarray):
    """Stable sort rows of ``keys`` (N, n_words) lexicographically; returns
    (sorted_keys, sorted_payloads..., order). numpy path."""
    order = lexsort_keys(keys)
    return (keys[order],) + tuple(p[order] for p in payloads) + (order,)


def keys_less_equal(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """Elementwise lexicographic a <= b for (..., n_words) uint32 keys."""
    nw = a.shape[-1]
    le = np.ones(np.broadcast_shapes(a.shape[:-1], b.shape[:-1]), dtype=bool)
    decided = np.zeros_like(le)
    for i in range(nw):
        lt = a[..., i] < b[..., i]
        gt = a[..., i] > b[..., i]
        le = np.where(~decided & lt, True, le)
        le = np.where(~decided & gt, False, le)
        decided |= lt | gt
    return le


def keys_less(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """Elementwise lexicographic a < b for (..., n_words) unsigned keys."""
    return ~keys_less_equal(b, a)  # total order: a < b == not (b <= a)


def searchsorted_keys(sorted_keys: np.ndarray, query_key: np.ndarray) -> int:
    """Binary search for the insertion point of ``query_key`` (n_words,) in
    lexicographically sorted ``sorted_keys`` (N, n_words)."""
    lo, hi = 0, sorted_keys.shape[0]
    qt = tuple(int(x) for x in query_key)
    while lo < hi:
        mid = (lo + hi) // 2
        if tuple(int(x) for x in sorted_keys[mid]) < qt:
            lo = mid + 1
        else:
            hi = mid
    return lo


def searchsorted_keys_batch(
    sorted_keys: np.ndarray, query_keys: np.ndarray
) -> np.ndarray:
    """Vectorized :func:`searchsorted_keys` for a whole query batch.

    Left insertion points of ``query_keys`` (m, n_words) into the
    lexicographically sorted ``sorted_keys`` (N, n_words), returned as an
    (m,) int64 array. All m binary searches advance in lockstep as pure
    array ops: each probe is one fancy-indexed gather of the m midpoints
    plus one vectorized lexicographic compare (on u64-packed columns, so
    half the word comparisons), O(log N) probes total — the batched gate
    of the approximate serving tier."""
    sorted_keys = np.asarray(sorted_keys)
    query_keys = np.asarray(query_keys)
    n = int(sorted_keys.shape[0])
    m = int(query_keys.shape[0])
    lo = np.zeros(m, np.int64)
    if n == 0 or m == 0:
        return lo
    hi = np.full(m, n, np.int64)
    sk = pack_u64(sorted_keys)
    qk = pack_u64(query_keys)
    while True:
        active = lo < hi
        if not active.any():
            break
        mid = np.where(active, (lo + hi) >> 1, 0)  # finished lanes gather row 0
        less = keys_less(sk[mid], qk)  # sorted[mid] < query, elementwise
        lo = np.where(active & less, mid + 1, lo)
        hi = np.where(active & ~less, mid, hi)
    return lo
