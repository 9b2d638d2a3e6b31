#!/usr/bin/env python3
"""Bring-up check of the served kNN path on one TPU chip.

    python chip_smoke.py                   # one chip, 4,000,000 series
    python chip_smoke.py --series 400000   # a smaller rehearsal
    python chip_smoke.py --four-chips      # only the mesh path, on four chips

One process drives the system through its own entry points:

a. the compiled ``screen_select``/``screen_select_quant`` kernels against
   their jnp oracles at served shapes;
b. async BTP ingest of ``--series`` random-walk series of length 256 into a
   ``StreamingIndex`` (the raw arena reaches 2^22 rows, about 4 GiB, on the
   device at the default size), a drain, then a few hundred single-query
   requests through the ``Gateway``: exact and approximate, with and
   without a time window. Every exact answer must equal a plain f64
   brute-force scan of the same generated data, id for id; the approximate
   tier's recall@k is reported;
c. a report of the device, compile and warm-up seconds and the verification
   engine's counters. The run fails if the device engine served nothing.

``--four-chips`` runs only ``window_knn_batch(..., shard="mesh")`` over the
default (queries x runs) mesh of four chips, against the single-device
engine and the same f64 reference.

The last line of standard output is ``{"ok": true, "device": {...}}``. When
JAX finds no TPU the script exits non-zero and prints no result.
"""
from __future__ import annotations

import argparse
import json
import logging
import os
import resource
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

SERIES_LEN = 256
BATCH = 50_000  # series per ingest batch; the batch index is its timestamp
K = 10
SLACK = 8  # the engine's slate slack beyond k
REQUESTS = 256  # single-query gateway requests in the serving phase
MESH_QUERIES = 64  # queries per batch in the four-chip phase


def log(msg: str) -> None:
    print(msg, flush=True)


# ------------------------------------------------------------------ data
def generate(n_series: int, seed: int, batch: int = BATCH) -> list:
    """The collection as timestamped batches: batch ``b`` holds random
    walks from its own seed and carries timestamp ``b``."""
    from repro.data.synthetic import random_walk

    sizes = [min(batch, n_series - s) for s in range(0, n_series, batch)]
    return [random_walk(n, SERIES_LEN, seed=seed * 65536 + b)
            for b, n in enumerate(sizes)]


def queries(n: int, seed: int) -> np.ndarray:
    """Fresh random walks, from a seed no data batch uses."""
    from repro.data.synthetic import random_walk

    return random_walk(n, SERIES_LEN, seed=seed * 65536 + 65535)


def brute_force(batches: list, Q: np.ndarray, k: int, window=None,
                chunk: int = 1 << 16) -> tuple[np.ndarray, np.ndarray]:
    """Exact kNN by a plain f64 scan of the generated batches, independent
    of the index: a matmul-form shortlist of k + 8 rows per query, chunk by
    chunk, then an exact difference-form re-rank with ties broken by id.
    Ids are positions in ingest order. ``window`` = (t0, t1) keeps only the
    batches whose timestamp lies in it. Returns ((m, k) f64, (m, k) int64).
    """
    Q64 = Q.astype(np.float64)
    qn = np.einsum("md,md->m", Q64, Q64)
    m, keep = Q.shape[0], k + SLACK
    best_d = np.full((m, 0), np.inf)
    best_i = np.zeros((m, 0), np.int64)
    base = 0
    for t, x in enumerate(batches):
        if window is None or window[0] <= t <= window[1]:
            for s in range(0, x.shape[0], chunk):
                xc = x[s:s + chunk].astype(np.float64)
                d2 = qn[:, None] + np.einsum("nd,nd->n", xc, xc)[None, :] \
                    - 2.0 * (Q64 @ xc.T)
                ids = np.broadcast_to(np.arange(base + s, base + s + xc.shape[0]),
                                      d2.shape)
                best_d = np.concatenate([best_d, d2], axis=1)
                best_i = np.concatenate([best_i, ids], axis=1)
                if best_d.shape[1] > keep:
                    part = np.argpartition(best_d, keep - 1, axis=1)[:, :keep]
                    best_d = np.take_along_axis(best_d, part, axis=1)
                    best_i = np.take_along_axis(best_i, part, axis=1)
        base += x.shape[0]
    starts = np.cumsum([0] + [x.shape[0] for x in batches])
    rows = np.stack([batches[b][i - starts[b]] for b, i in zip(
        np.searchsorted(starts, best_i.ravel(), side="right") - 1,
        best_i.ravel())]).reshape(m, -1, SERIES_LEN).astype(np.float64)
    exact = ((rows - Q64[:, None, :]) ** 2).sum(axis=-1)
    order = np.lexsort((best_i, exact), axis=1)[:, :k]
    return (np.take_along_axis(exact, order, axis=1),
            np.take_along_axis(best_i, order, axis=1))


def check_exact(vals, ids, ref_vals, ref_ids, what: str) -> None:
    """Exact answers: ids equal to the reference; distances, which the
    engine returns in f32, within f32 rounding of the f64 values."""
    bad = np.nonzero((np.asarray(ids) != ref_ids).any(axis=1))[0]
    if bad.size:
        i = bad[0]
        raise AssertionError(
            f"{what}: {bad.size} answers differ from the f64 brute force; "
            f"first query {i}: got {ids[i].tolist()} want "
            f"{ref_ids[i].tolist()}")
    np.testing.assert_allclose(vals, ref_vals, rtol=1e-6, err_msg=what)


def recall(ids, ref_ids) -> float:
    k = ref_ids.shape[1]
    return float(np.mean([len(set(a.tolist()) & set(b.tolist())) / k
                          for a, b in zip(ids, ref_ids)]))


# ------------------------------------------------------------- a. kernels
KERNEL_SHAPES = [  # (m, n, d, slate): the engine's chunk, served rungs
    (64, 1536, 256, K + SLACK),
    (64, 65536, 256, 50 + SLACK),
    (16, 4096, 128, 1 + SLACK),
]


def check_kernels(shapes=KERNEL_SHAPES, seed: int = 0) -> None:
    """The fused screen kernels against their jnp oracles for f32, bf16 and
    int8 candidate tables: slate ids equal up to near-ties (see
    :func:`check_slate`), values within f32 rounding. The oracle runs at
    full f32 matmul precision, as the kernel does."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops, ref

    rng = np.random.default_rng([seed, 7])
    for m, n, d, s in shapes:
        q = rng.standard_normal((m, d)).astype(np.float32)
        xf = rng.standard_normal((n, d)).astype(np.float32)
        amax = np.abs(xf).max(axis=1)
        scale = np.where(amax > 0, amax / 127.0, 1.0).astype(np.float32)
        x8 = np.clip(np.rint(xf / scale[:, None]), -127, 127).astype(np.int8)
        deq = x8.astype(np.float64) * scale[:, None]
        xb = jnp.asarray(xf, jnp.bfloat16)
        xbf = np.asarray(xb.astype(jnp.float32))
        n2f, n2b, n2q = _n2(xf), _n2(xbf), _n2(deq)
        cases = {  # candidate norms, kernel(), oracle(width)
            "f32": (n2f, lambda: ops.screen_select(q, xf, n2f, s),
                    lambda w: ref.screen_select_ref(q, xf, n2f, w)),
            "bf16": (n2b, lambda: ops.screen_select(q, xb, n2b, s),
                     lambda w: ref.screen_select_ref(q, xb, n2b, w)),
            "int8": (n2q, lambda: ops.screen_select_quant(q, x8, scale, n2q, s),
                     lambda w: ref.screen_select_quant_ref(q, x8, scale, n2q,
                                                           w)),
        }
        for name, (xn2, kernel, oracle) in cases.items():
            t0 = time.perf_counter()
            v, i, qn2 = jax.block_until_ready(kernel())
            first = time.perf_counter() - t0
            with jax.default_matmul_precision("highest"):
                full_v, full_i, rqn2 = oracle(n)  # every candidate, ranked
            what = f"{name} m={m} n={n} d={d} slate={s}"
            swapped, gap = check_slate(v, i, full_v, full_i, rqn2, xn2, what)
            np.testing.assert_allclose(np.asarray(qn2), np.asarray(rqn2),
                                       rtol=1e-6, err_msg=what)
            log(f"[kernels] {what}: ids equal except {swapped} of {i.size} "
                f"positions swapped within near-ties (largest gap {gap:.3g} "
                f"of its tolerance), max |dv|="
                f"{np.abs(np.asarray(v) - np.asarray(full_v)[:, :s]).max():.3g},"
                f" first call (compile + run) {first:.2f}s")


def check_slate(v, i, full_v, full_i, qn2, xn2, what: str) -> tuple[int, float]:
    """A kernel slate (m, s) against its oracle's full ranking (m, n).

    Values agree position by position within f32 rounding (the tolerances
    of the former compiled-kernel tests) and no id repeats. Ids equal the
    oracle's, except that the two summation orders may rank a near-tie
    differently: position p may hold another id only if the oracle's
    distance for that id lies within four f32 ulps of the oracle's
    distance at p, ulps of |q|^2 + |x|^2 (the magnitude the distance is
    cancelled from) for the larger |x|^2 of the two ids. Returns how many
    positions hold a swapped id and the largest swap gap as a fraction of
    its tolerance."""
    v, i, full_v, full_i, qn2, xn2 = (np.asarray(a) for a in (
        v, i, full_v, full_i, qn2, xn2))
    s = i.shape[1]
    rv, ri = full_v[:, :s], full_i[:, :s]
    np.testing.assert_allclose(v, rv, rtol=1e-5, atol=1e-3, err_msg=what)
    by_id = np.empty_like(full_v)  # the oracle's distance of every id
    np.put_along_axis(by_id, full_i, full_v, axis=1)
    got = np.take_along_axis(by_id, i, axis=1)
    tol = 4 * np.finfo(np.float32).eps * (
        qn2[:, None] + np.maximum(xn2[i], xn2[ri]))
    swap = i != ri
    gap = np.where(swap, np.abs(got - rv) / tol, 0.0)
    for row in range(i.shape[0]):
        if len(set(i[row].tolist())) != s or (gap[row] > 1).any():
            p = int(np.argmax(gap[row]))
            raise AssertionError(
                f"{what}: query {row} slate {i[row].tolist()} repeats ids or "
                f"holds id {i[row, p]} at position {p}, where the oracle has "
                f"id {ri[row, p]} at {rv[row, p]!r} and id {i[row, p]} at "
                f"{got[row, p]!r}")
    return int(swap.sum()), float(gap.max())


def _n2(x) -> np.ndarray:
    x = np.asarray(x, np.float64)
    return np.einsum("nd,nd->n", x, x).astype(np.float32)


# --------------------------------------------------- b. ingest and serving
def build_index(batches: list):
    """Async BTP ingest of the batches (the ``serve.py --ingest async``
    stream configuration), drained before serving so that every answer
    comes from one known state of the data."""
    from repro.core import StreamConfig, StreamingIndex, SummarizationConfig

    idx = StreamingIndex(StreamConfig(
        scheme="BTP", summarization=SummarizationConfig(
            series_len=SERIES_LEN, n_segments=16, card_bits=8),
        buffer_entries=4096, growth_factor=4, block_size=512,
        ingest="async"))
    t0 = time.perf_counter()
    for t, x in enumerate(batches):
        idx.ingest(x, np.full(x.shape[0], t, np.int64))
    if not idx.drain(timeout=1800):
        raise AssertionError("ingest did not drain")
    log(f"[ingest] {idx.raw.n} series in {len(batches)} batches, "
        f"{idx.n_partitions} runs, ingest + drain {time.perf_counter() - t0:.1f}s")
    return idx


def serve_and_check(idx, batches: list, n_requests: int, seed: int) -> dict:
    """A few hundred single-query requests through the gateway, checked
    against the f64 brute force. Four request kinds, interleaved: exact or
    approximate (target recall 1.0 or 0.9), over the whole history or over
    the newest tenth of the timestamps."""
    from repro.core import Gateway, GatewayConfig
    from repro.core.verify_engine import get_engine

    engine = get_engine()
    t0 = time.perf_counter()
    view = idx.raw.device_view()
    log(f"[arena] {view.n} rows in a capacity of {view.cap} x {view.table.shape[1]}"
        f" {view.dtype}, {view.nbytes} bytes; engine arena_bytes="
        f"{engine.stats['arena_bytes']}; built in {time.perf_counter() - t0:.1f}s")
    log_memory("arena")
    gw = Gateway(idx, GatewayConfig(deadline_ms=5.0, slo_p99_ms=1e9,
                                    max_batch=64, k=K))
    try:
        t0 = time.perf_counter()
        n_traces = gw.prewarm([idx.raw.n])
        log(f"[prewarm] {n_traces} verification traces compiled and run in "
            f"{time.perf_counter() - t0:.1f}s")
        log_memory("prewarm")
        traces_warm = engine.stats["traces"]
        calls_before = engine.stats["calls"]

        n_ts = len(batches)
        window = (n_ts - max(1, n_ts // 10), n_ts - 1)
        Q = queries(n_requests, seed)
        kinds = [(tier, win) for tier in ("exact", "approx")
                 for win in (None, window)]
        t0 = time.perf_counter()
        tickets = []
        for i in range(n_requests):
            tier, win = kinds[i % len(kinds)]
            tickets.append(gw.submit(
                Q[i], window=win,
                target_recall=1.0 if tier == "exact" else 0.9))
        resps = [t.result(timeout=900) for t in tickets]
        log(f"[serve] {n_requests} requests answered in "
            f"{time.perf_counter() - t0:.1f}s; window={window}")
    finally:
        gw.close()

    t0 = time.perf_counter()
    ref = {}
    for win in (None, window):
        sel = [i for i in range(n_requests) if kinds[i % len(kinds)][1] == win]
        rv, ri = brute_force(batches, Q[sel], K, window=win)
        for j, i in enumerate(sel):
            ref[i] = (rv[j], ri[j])
    log(f"[reference] f64 brute force in {time.perf_counter() - t0:.1f}s")
    log_memory("reference")

    served = {"exact": 0, "approx": 0}
    recalls = {None: [], window: []}
    for i, r in enumerate(resps):
        served[r.tier_served] += 1
        rv, ri = ref[i]
        win = kinds[i % len(kinds)][1]
        if r.tier_served == "exact":
            check_exact(r.vals[None], r.ids[None], rv[None], ri[None],
                        f"exact request {i} window={win}")
        else:
            recalls[win].append(recall(r.ids[None], ri[None]))
    if not served["exact"] or not served["approx"]:
        raise AssertionError(f"both tiers must serve: {served}")
    log(f"[check] {served['exact']} exact answers equal the f64 brute force "
        f"id for id; approx recall@{K}: whole history "
        f"{np.mean(recalls[None]):.4f}, window {np.mean(recalls[window]):.4f}")
    st = engine.stats
    calls = st["calls"] - calls_before
    log(f"[engine] calls={st['calls']} (serving {calls}) screened="
        f"{st['screened']} traces={st['traces']} hits={st['hits']} "
        f"fallbacks={st['fallbacks']} batch_hist="
        f"{dict(sorted(st['batch_hist'].items()))}")
    log(f"[engine] post-warm-up retraces={st['traces'] - traces_warm}")
    if calls == 0:
        raise AssertionError("the device engine served no pass: the host "
                             "tail answered everything")
    return {"served": served, "calls": calls}


# ---------------------------------------------------------- four chips
def mesh_check(idx, batches: list, n_queries: int, seed: int) -> None:
    """The ``shard="mesh"`` exact tier over the default (queries x runs)
    mesh against the single-device engine and the f64 brute force, over
    the whole history and over the newest tenth of the timestamps."""
    from repro.core.distributed import default_batch_mesh

    mesh = default_batch_mesh()
    log(f"[mesh] shape {dict(mesh.shape)}, devices "
        f"{[[d.id for d in row] for row in mesh.devices]}")
    Q = queries(n_queries, seed)
    # each mesh screen logs where its own inputs landed
    placement = logging.getLogger("repro.core.distributed")
    handler = _PrintHandler("[mesh] ")
    placement.addHandler(handler)
    placement.setLevel(logging.DEBUG)
    n_ts = len(batches)
    try:
        for t0, t1 in ((0, n_ts - 1), (n_ts - max(1, n_ts // 10), n_ts - 1)):
            s = time.perf_counter()
            mv, mi, _ = idx.window_knn_batch(Q, t0, t1, k=K, shard="mesh")
            mesh_s = time.perf_counter() - s
            sv, si, _ = idx.window_knn_batch(Q, t0, t1, k=K)
            rv, ri = brute_force(batches, Q, K, window=(t0, t1))
            np.testing.assert_array_equal(mi, si)
            np.testing.assert_array_equal(mv, sv)
            check_exact(mv, mi, rv, ri, f"mesh window=({t0}, {t1})")
            log(f"[mesh] window ({t0}, {t1}): {n_queries} queries, mesh "
                f"answers equal the single-device engine and the f64 brute "
                f"force (mesh batch {mesh_s:.1f}s)")
            log_memory(f"mesh window ({t0}, {t1})")
    finally:
        placement.removeHandler(handler)
        placement.setLevel(logging.NOTSET)
    want = [f"device {d.id} holds" for d in mesh.devices.flat]
    spread = [all(w in half for w in want)
              for line in handler.lines for half in line.split("; candidates")]
    if not spread or not all(spread):
        raise AssertionError("the mesh screen's queries or candidates are not "
                             f"split over all of {mesh.devices.size} devices")


class _PrintHandler(logging.Handler):
    """Log records to standard output, after a prefix."""

    def __init__(self, prefix: str):
        super().__init__()
        self.prefix = prefix
        self.lines: list[str] = []

    def emit(self, record):
        self.lines.append(record.getMessage())
        log(self.prefix + self.lines[-1])


# ------------------------------------------------------------------ main
def log_memory(stage: str) -> None:
    """Device peak bytes (where the backend reports them) and the host's
    current and peak resident set, after a phase."""
    import jax

    dev_peak = (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")
    with open("/proc/self/statm") as f:
        rss = int(f.read().split()[1]) * os.sysconf("SC_PAGE_SIZE")
    log(f"[memory] after {stage}: device peak_bytes_in_use={dev_peak}, "
        f"host RSS {rss} bytes, host max RSS "
        f"{resource.getrusage(resource.RUSAGE_SELF).ru_maxrss << 10} bytes")


def host_memory_available() -> tuple[int, str]:
    """Bytes this process may still allocate: the kernel's MemAvailable
    (free memory plus the page cache it can reclaim), capped by the
    headroom under the cgroup's memory.max where one is set, the cgroup's
    page cache counted as reclaimable there too."""
    with open("/proc/meminfo") as f:
        info = dict(line.split(":", 1) for line in f)
    avail = int(info["MemAvailable"].split()[0]) << 10
    try:
        with open("/sys/fs/cgroup/memory.max") as f:
            limit = f.read().strip()
        with open("/sys/fs/cgroup/memory.current") as f:
            used = int(f.read())
        with open("/sys/fs/cgroup/memory.stat") as f:
            cache = int(dict(line.split() for line in f)["file"])
    except (OSError, KeyError, ValueError):
        return avail, "MemAvailable"
    if limit != "max" and int(limit) - used + cache < avail:
        return int(limit) - used + cache, "cgroup memory.max headroom"
    return avail, "MemAvailable"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--series", type=int, default=4_000_000,
                    help="collection size (default: an arena of 2^22 rows)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the mesh path over four chips")
    args = ap.parse_args(argv)

    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX sees {devs[0].platform}); "
              "this check runs only on a TPU", file=sys.stderr)
        return 2
    want = 4 if args.four_chips else 1
    if len(devs) < want:
        print(f"chip_smoke: {want} TPU devices needed, found {len(devs)}",
              file=sys.stderr)
        return 2

    # the generated batches, the raw store's concatenated copy and the
    # arena's f32 staging buffer each hold the whole collection on the
    # host; at 4M series the host's peak RSS was about 7.8 times the
    # collection's bytes on one v5e chip and 17.8 times on four, where the
    # mesh path also gathers and centres the candidate table on the host
    need = (18 if args.four_chips else 8) * args.series * SERIES_LEN * 4
    avail, source = host_memory_available()
    log(f"[host] {args.series} series need about {need} bytes of host RAM; "
        f"{avail} bytes available ({source})")
    if need > avail:
        print(f"chip_smoke: {args.series} series need about {need >> 30} GiB "
              f"of host RAM, {avail >> 30} GiB available ({source})",
              file=sys.stderr)
        return 2

    from repro.launch.jax_cache import use_persistent_cache

    log(f"[device] {devs[0].device_kind} x {len(devs)}; compile cache at "
        f"{use_persistent_cache()}")
    t_all = time.perf_counter()
    if not args.four_chips:
        t0 = time.perf_counter()
        check_kernels(seed=args.seed)
        log(f"[kernels] phase {time.perf_counter() - t0:.1f}s")
    t0 = time.perf_counter()
    batches = generate(args.series, args.seed)
    log(f"[data] {args.series} random walks x {SERIES_LEN} in "
        f"{len(batches)} batches, generated in {time.perf_counter() - t0:.1f}s")
    idx = build_index(batches)
    log_memory("ingest")
    try:
        if args.four_chips:
            mesh_check(idx, batches, MESH_QUERIES, args.seed)
        else:
            serve_and_check(idx, batches, REQUESTS, args.seed)
    finally:
        idx.close()
    log(f"[done] {time.perf_counter() - t_all:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
