"""The plain reference: exact kNN by an f64 scan of the generated batches.

It imports nothing of the system under test and takes nothing the system
made. Batch ``t`` of the collection carries timestamp ``t`` and global ids
are positions in ingest order, so a time window ``(t0, t1)`` is the batches
``t0 .. t1``. The comparison helpers turn served answers and reference
answers into the numbers that decide ``correct``.
"""
from __future__ import annotations

import ml_dtypes
import numpy as np

SLACK = 8  # shortlist rows beyond k before the exact re-rank


def _rounded(x: np.ndarray, precision: str) -> np.ndarray:
    """``x`` in f64, after rounding to ``precision`` (f32 or bf16)."""
    if precision == "bf16":
        x = np.asarray(x, np.float32).astype(ml_dtypes.bfloat16)
    return np.asarray(x, np.float64)


def knn(batches: list, Q: np.ndarray, k: int, windows=None,
        precision: str = "f32", chunk: int = 1 << 16):
    """Exact kNN of each query over the batches its window admits.

    A matmul-form shortlist of k + 8 rows per query, chunk by chunk, then
    an exact difference-form re-rank, ties broken by id. ``windows`` is
    None (whole history) or one ``(t0, t1)`` or None per query.
    ``precision="bf16"`` rounds data and queries to bfloat16 first: the
    low-precision control. Returns ((m, k) f64 squared distances, (m, k)
    int64 ids, -1 where a window holds fewer than k rows)."""
    Q64 = _rounded(Q, precision)
    m, keep = Q64.shape[0], k + SLACK
    qn = np.einsum("md,md->m", Q64, Q64)
    best_d = np.full((m, keep), np.inf)
    best_i = np.full((m, keep), -1, np.int64)
    starts = np.cumsum([0] + [x.shape[0] for x in batches])
    for t, x in enumerate(batches):
        if windows is None:
            sel = np.arange(m)
        else:
            sel = np.array([i for i, w in enumerate(windows)
                            if w is None or w[0] <= t <= w[1]], np.int64)
        if not sel.size:
            continue
        for s in range(0, x.shape[0], chunk):
            xc = _rounded(x[s:s + chunk], precision)
            d2 = (qn[sel, None] + np.einsum("nd,nd->n", xc, xc)[None, :]
                  - 2.0 * (Q64[sel] @ xc.T))
            ids = np.broadcast_to(
                np.arange(starts[t] + s, starts[t] + s + xc.shape[0]), d2.shape)
            cd = np.concatenate([best_d[sel], d2], axis=1)
            ci = np.concatenate([best_i[sel], ids], axis=1)
            part = np.argpartition(cd, keep - 1, axis=1)[:, :keep]
            best_d[sel] = np.take_along_axis(cd, part, axis=1)
            best_i[sel] = np.take_along_axis(ci, part, axis=1)
    exact = distances(batches, Q, best_i, precision)
    order = np.lexsort((best_i, exact), axis=1)[:, :k]
    ids = np.take_along_axis(best_i, order, axis=1)
    return np.take_along_axis(exact, order, axis=1), ids


def distances(batches: list, Q: np.ndarray, ids: np.ndarray,
              precision: str = "f32") -> np.ndarray:
    """Exact f64 squared distances, difference form, from each query to
    the rows ``ids`` (m, j) name; inf where an id is -1."""
    starts = np.cumsum([0] + [x.shape[0] for x in batches])
    flat = np.asarray(ids, np.int64).ravel()
    ok = (flat >= 0) & (flat < starts[-1])
    b = np.searchsorted(starts, np.where(ok, flat, 0), side="right") - 1
    rows = np.stack([batches[bi][i - starts[bi]] for bi, i in
                     zip(b, np.where(ok, flat, 0))]) if flat.size else \
        np.zeros((0, Q.shape[1]), np.float32)
    rows = _rounded(rows, precision).reshape(ids.shape + (Q.shape[1],))
    d2 = ((rows - _rounded(Q, precision)[:, None, :]) ** 2).sum(axis=-1)
    return np.where(ok.reshape(ids.shape), d2, np.inf)


def id_mismatches(ids, ref_ids) -> int:
    """Answers whose id list differs from the reference's."""
    return int((np.asarray(ids) != np.asarray(ref_ids)).any(axis=1).sum())


def dist_rel_err(vals, true_vals) -> float:
    """Largest relative gap between served and true squared distances."""
    vals = np.asarray(vals, np.float64)
    true_vals = np.asarray(true_vals, np.float64)
    fin = np.isfinite(true_vals)
    if not fin.any():
        return 0.0
    gap = np.abs(vals[fin] - true_vals[fin])
    return float((gap / np.maximum(true_vals[fin], 1e-30)).max())


def bad_answers(ids, windows, batches: list, k: int) -> int:
    """Approximate answers that break what every answer guarantees: k
    distinct ids, each a stored row inside the query's window."""
    starts = np.cumsum([0] + [x.shape[0] for x in batches])
    bad = 0
    for row, w in zip(np.asarray(ids), windows):
        lo, hi = (0, starts[-1]) if w is None else (starts[w[0]],
                                                    starts[w[1] + 1])
        if (len(set(row.tolist())) != k or (row < lo).any()
                or (row >= hi).any()):
            bad += 1
    return bad


def recall(ids, ref_ids) -> float:
    """Mean share of each reference answer's ids that the served one holds."""
    k = ref_ids.shape[1]
    return float(np.mean([len(set(a.tolist()) & set(b.tolist())) / k
                          for a, b in zip(ids, ref_ids)]))
