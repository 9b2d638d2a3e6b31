"""Epoch-based run registry + background ingest pipeline (concurrent
ingest/query semantics).

The contract under test: every query answers from ONE pinned immutable
snapshot — brute force over that snapshot's entries — no matter how many
flushes/merges publish concurrently; runs a merge replaces are retired
only after the last pinned epoch that could see them drops; and the
cascading-merge driver is iterative (a deep cascade must not scale the
Python stack with the level count)."""
import sys
import threading

import numpy as np
import pytest

from repro.core import (
    CLSM,
    CLSMConfig,
    RawStore,
    StreamConfig,
    StreamingIndex,
    SummarizationConfig,
)
from repro.core.run_registry import BufferChunk, RunRegistry

CFG = SummarizationConfig(series_len=64, n_segments=8, card_bits=6)


def _series(n, seed):
    rng = np.random.default_rng(seed)
    return rng.standard_normal((n, 64)).astype(np.float32).cumsum(axis=1)


def _chunk(n, seed, t=0, id0=0):
    return BufferChunk(
        series=_series(n, seed),
        ids=np.arange(id0, id0 + n, dtype=np.int64),
        ts=np.full(n, t, np.int64),
    )


# ---------------------------------------------------------------------------
# registry mechanics
# ---------------------------------------------------------------------------
def test_registry_snapshots_are_immutable_and_epochs_advance():
    reg = RunRegistry()
    s0 = reg.current()
    assert s0.epoch == 0 and s0.buffer_n == 0
    s1 = reg.append_buffer(_chunk(10, seed=0))
    assert s1.epoch == 1 and s1.buffer_n == 10
    assert s0.buffer_n == 0  # the old snapshot did not change
    taken, s2 = reg.take_for_flush(6)
    assert taken.n == 6 and s2.epoch == 2
    assert s2.buffer_n == 4 and s2.flushing_n == 6
    # entries live in exactly one place at every epoch
    for snap in (s1, s2):
        total = snap.buffer_n + snap.flushing_n
        assert total == 10
    reg.publish_flush(taken, run=object())
    s3 = reg.current()
    assert s3.epoch == 3 and s3.flushing_n == 0 and s3.n_runs == 1
    assert s3.buffer_n == 4


def test_registry_take_preserves_fifo_ids():
    reg = RunRegistry()
    reg.append_buffer(_chunk(5, seed=1, id0=0))
    reg.append_buffer(_chunk(5, seed=2, id0=5))
    taken, _ = reg.take_for_flush(7)
    np.testing.assert_array_equal(taken.ids, np.arange(7))
    snap = reg.current()
    np.testing.assert_array_equal(
        np.concatenate([c.ids for c in snap.buffer]), np.arange(7, 10))


def test_registry_take_copies_only_what_it_takes():
    """A flush takes whole chunks and the head of one more; the rest of the
    backlog stays as it was, so a large async backlog costs no copies."""
    reg = RunRegistry()
    chunks = [_chunk(5, seed=s, t=s, id0=5 * s) for s in range(4)]
    for c in chunks:
        reg.append_buffer(c)
    taken, snap = reg.take_for_flush(7)
    np.testing.assert_array_equal(taken.ids, np.arange(7))
    np.testing.assert_array_equal(taken.ts, np.concatenate(
        [chunks[0].ts, chunks[1].ts[:2]]))
    np.testing.assert_array_equal(taken.series, np.concatenate(
        [chunks[0].series, chunks[1].series[:2]]))
    assert snap.buffer[1] is chunks[2] and snap.buffer[2] is chunks[3]
    np.testing.assert_array_equal(
        np.concatenate([c.ids for c in snap.buffer]), np.arange(7, 20))
    np.testing.assert_array_equal(snap.buffer[0].ts, chunks[1].ts[2:])


class _FakeRun:
    """A run stand-in that records arena releases."""

    def __init__(self):
        self.released = 0

    def release_device_view(self):
        self.released += 1


def test_retired_runs_survive_until_last_pin_drops():
    reg = RunRegistry()
    victims = [_FakeRun(), _FakeRun()]
    merged = _FakeRun()
    for v in victims:
        c = _chunk(1, seed=3)
        reg.append_buffer(c)
        t, _ = reg.take_for_flush(1)
        reg.publish_flush(t, v)
    with reg.pin() as snap:
        assert [r is v for r, v in zip(snap.level_runs(0), victims)]
        reg.publish_merge(0, victims, merged)
        new = reg.current()
        assert list(new.level_runs(0)) == [] and new.level_runs(1) == (merged,)
        # pinned epoch still references the victims: nothing released
        assert reg.retired_pending == 2
        assert all(v.released == 0 for v in victims)
        # the pinned snapshot still sees the pre-merge world
        assert snap.level_runs(0) == tuple(victims)
    # pin dropped -> deferred retirement fires
    assert reg.retired_pending == 0
    assert all(v.released == 1 for v in victims)
    assert reg.released_runs == 2


def test_unpinned_retirement_is_immediate():
    reg = RunRegistry()
    v = _FakeRun()
    c = _chunk(1, seed=4)
    reg.append_buffer(c)
    t, _ = reg.take_for_flush(1)
    reg.publish_flush(t, v)
    reg.publish_merge(0, [v], _FakeRun())
    assert v.released == 1 and reg.retired_pending == 0


def test_overlapping_pins_release_once_all_drop():
    reg = RunRegistry()
    v = _FakeRun()
    c = _chunk(1, seed=5)
    reg.append_buffer(c)
    t, _ = reg.take_for_flush(1)
    reg.publish_flush(t, v)
    with reg.pin():
        with reg.pin():
            reg.publish_merge(0, [v], _FakeRun())
            assert v.released == 0
        assert v.released == 0  # the outer (older) pin still holds it
    assert v.released == 1


# ---------------------------------------------------------------------------
# CLSM on the registry
# ---------------------------------------------------------------------------
def test_clsm_plan_records_epoch_and_is_snapshot_stable():
    raw = RawStore(64)
    lsm = CLSM(CLSMConfig(summarization=CFG, buffer_entries=64,
                          growth_factor=2, block_size=32), disk=raw.disk)
    x = _series(200, seed=6)
    lsm.insert(x, raw.append(x), np.zeros(200, np.int64))
    snap = lsm.registry.current()
    plan = lsm.plan(_series(3, seed=7), raw=raw, snapshot=snap)
    assert plan.epoch == snap.epoch
    # more ingest bumps the epoch; a plan built from the old snapshot
    # keeps planning the old run set
    x2 = _series(200, seed=8)
    lsm.insert(x2, raw.append(x2), np.ones(200, np.int64))
    assert lsm.registry.current().epoch > snap.epoch
    plan_old = lsm.plan(_series(3, seed=7), raw=raw, snapshot=snap)
    assert len(plan_old.sources) == len(plan.sources)


def test_maybe_merge_is_iterative_on_deep_cascades(monkeypatch):
    """128 level-0 runs at growth_factor=2 cascade through 7 levels in one
    _maybe_merge call: the driver must not re-enter itself (worklist, not
    recursion) and the whole cascade must fit in a near-flat stack."""
    raw = RawStore(64)
    lsm = CLSM(CLSMConfig(summarization=CFG, buffer_entries=8,
                          growth_factor=2, block_size=8, merge=False),
               disk=raw.disk)
    for i in range(128):
        x = _series(8, seed=100 + i)
        lsm.insert(x, raw.append(x), np.full(8, i, np.int64))
    assert lsm.n_runs == 128
    lsm.cfg.merge = True

    depth = {"cur": 0, "max": 0}
    orig = CLSM._maybe_merge

    def wrapped(self, level):
        depth["cur"] += 1
        depth["max"] = max(depth["max"], depth["cur"])
        try:
            return orig(self, level)
        finally:
            depth["cur"] -= 1

    monkeypatch.setattr(CLSM, "_maybe_merge", wrapped)
    limit = sys.getrecursionlimit()
    try:
        # a recursive cascade would add O(levels) frames; the iterative
        # driver adds O(1), so a tight headroom still completes
        def _frames():
            f, n = sys._getframe(), 0
            while f is not None:
                f, n = f.f_back, n + 1
            return n

        sys.setrecursionlimit(_frames() + 40)
        lsm._maybe_merge(0)
    finally:
        sys.setrecursionlimit(limit)
    assert depth["max"] == 1  # never re-entered: the worklist did the cascade
    assert lsm.n_runs == 1 and lsm.n_merges == 127
    # the collapsed index still answers exactly
    q = _series(1, seed=9)[0]
    res, _ = lsm.knn_exact(q, k=3, raw=raw)
    from repro.core import ed2

    bf = np.sort(ed2(q, raw._all()))[:3]
    np.testing.assert_allclose([d for d, _ in res], bf, rtol=1e-5)


def test_async_ingest_matches_sync_after_drain():
    out = {}
    for mode in ("sync", "async"):
        idx = StreamingIndex(StreamConfig(scheme="BTP", summarization=CFG,
                                          buffer_entries=256, growth_factor=3,
                                          block_size=64, ingest=mode))
        for b in range(12):
            idx.ingest(_series(150, seed=20 + b), np.full(150, b, np.int64))
        assert idx.drain(timeout=120)
        vals, gids, _ = idx.window_knn_batch(_series(4, seed=50), 2, 9, k=5)
        out[mode] = (vals, gids, idx.n_partitions,
                     sorted((lv, len(runs)) for lv, runs
                            in idx.lsm.registry.current().levels))
        idx.close()
    np.testing.assert_array_equal(out["sync"][0], out["async"][0])
    np.testing.assert_array_equal(out["sync"][1], out["async"][1])
    assert out["sync"][2] == out["async"][2]  # same run count
    assert out["sync"][3] == out["async"][3]  # same level structure


def test_ingest_lag_reports_backlog_and_drains():
    idx = StreamingIndex(StreamConfig(scheme="BTP", summarization=CFG,
                                      buffer_entries=512, growth_factor=2,
                                      block_size=64, ingest="async"))
    for b in range(8):
        idx.ingest(_series(300, seed=60 + b), np.full(300, b, np.int64))
    lag = idx.ingest_lag()
    assert set(lag) >= {"epoch", "lag_entries", "runs_pending_merge",
                        "snapshot_age_s"}
    assert idx.drain(timeout=120)
    lag = idx.ingest_lag()
    assert lag["lag_entries"] < 512  # only the sub-threshold tail remains
    assert lag["runs_pending_merge"] == 0
    idx.close()


def test_backpressure_below_flush_threshold_is_rejected():
    with pytest.raises(ValueError):
        StreamingIndex(StreamConfig(scheme="BTP", summarization=CFG,
                                    buffer_entries=2048, ingest="async",
                                    max_lag_entries=1024))


def test_insert_after_close_raises():
    idx = StreamingIndex(StreamConfig(scheme="BTP", summarization=CFG,
                                      buffer_entries=64, ingest="async"))
    idx.ingest(_series(32, seed=80), np.zeros(32, np.int64))
    idx.close()
    with pytest.raises(RuntimeError):
        idx.ingest(_series(32, seed=81), np.zeros(32, np.int64))


def test_drain_flush_buffer_flushes_the_subthreshold_tail():
    idx = StreamingIndex(StreamConfig(scheme="BTP", summarization=CFG,
                                      buffer_entries=4096, growth_factor=2,
                                      block_size=64, ingest="async"))
    idx.ingest(_series(300, seed=82), np.zeros(300, np.int64))
    assert idx.drain(flush_buffer=True, timeout=120)
    snap = idx.lsm.registry.current()
    assert snap.buffer_n == 0 and snap.flushing_n == 0
    assert idx.n_partitions >= 1  # the 300-entry tail became a run
    idx.close()


def test_insert_racing_close_raises(monkeypatch):
    """An insert blocked on backpressure while close() stops the worker must
    raise, not return as if the data will ever be flushed. The worker is
    pinned idle (``_work_available`` forced False) so the backlog genuinely
    strands: before the fix the waiter either hung forever or returned
    success for data nothing would ever flush."""
    import time

    from repro.core import IngestPipeline

    lsm = CLSM(CLSMConfig(summarization=CFG, buffer_entries=64, block_size=32))
    pipe = IngestPipeline(lsm, max_lag_entries=64)
    monkeypatch.setattr(pipe, "_work_available", lambda: False)
    errs = []

    def submit():
        try:
            for b in range(2):  # second batch pushes backlog past the cap
                pipe.insert(_series(64, seed=90 + b),
                            np.arange(b * 64, (b + 1) * 64, dtype=np.int64),
                            np.full(64, b, np.int64))
        except RuntimeError as e:
            errs.append(e)

    th = threading.Thread(target=submit)
    th.start()
    deadline = time.time() + 10
    while pipe._backlog() <= pipe.max_lag_entries and time.time() < deadline:
        time.sleep(0.01)  # wait until the insert is really blocked
    assert pipe._backlog() > pipe.max_lag_entries
    pipe.close(timeout=10)
    th.join(timeout=10)
    assert not th.is_alive()
    assert errs and "closed" in str(errs[0])


def test_worker_errors_surface_on_the_submitting_thread(monkeypatch):
    idx = StreamingIndex(StreamConfig(scheme="BTP", summarization=CFG,
                                      buffer_entries=64, growth_factor=2,
                                      block_size=32, ingest="async"))

    def boom(self):
        raise RuntimeError("flush exploded")

    monkeypatch.setattr(CLSM, "_flush", boom)
    with pytest.raises(RuntimeError):
        for b in range(8):
            idx.ingest(_series(64, seed=70 + b), np.full(64, b, np.int64))
            idx.drain(timeout=30)


# ---------------------------------------------------------------------------
# concurrent stress: queries racing background flush/merge
# ---------------------------------------------------------------------------
def _snapshot_bruteforce(snap, X_all, window, Q, k):
    """Exact top-k over exactly the pinned snapshot's entries (f64 diff
    form, cast f32 like the engine's re-rank)."""
    ids = [c.ids for c in snap.buffer + snap.flushing]
    ts = [c.ts for c in snap.buffer + snap.flushing]
    for r in snap.runs_newest_first():
        ids.append(r.ids)
        ts.append(r.ts)
    if ids and any(i.size for i in ids):
        gids = np.concatenate(ids)
        gts = np.concatenate(ts)
    else:
        gids = np.zeros(0, np.int64)
        gts = np.zeros(0, np.int64)
    if window is not None:
        keep = (gts >= window[0]) & (gts <= window[1])
        gids = gids[keep]
    vals = np.full((len(Q), k), np.inf, np.float32)
    out = np.full((len(Q), k), -1, np.int64)
    if gids.size == 0:
        return vals, out
    X = X_all[gids].astype(np.float64)
    d2 = ((X[None, :, :] - Q[:, None, :].astype(np.float64)) ** 2).sum(-1)
    d2 = d2.astype(np.float32)
    kk = min(k, gids.size)
    order = np.argsort(d2, axis=1, kind="stable")[:, :kk]
    vals[:, :kk] = np.take_along_axis(d2, order, axis=1)
    out[:, :kk] = gids[order]
    return vals, out


@pytest.mark.slow
def test_queries_racing_ingest_are_snapshot_consistent():
    """Thread-pool stress: batched window queries race background
    flush/merge publishes; every answer must equal brute force over that
    query's pinned snapshot."""
    idx = StreamingIndex(StreamConfig(scheme="BTP", summarization=CFG,
                                      buffer_entries=128, growth_factor=2,
                                      block_size=32, ingest="async"))
    n_ingest, bsz = 24, 100
    X_parts = [_series(bsz, seed=200 + b) for b in range(n_ingest)]
    X_all = np.concatenate(X_parts)
    errors: list = []
    stop = threading.Event()

    def worker(wid):
        rng = np.random.default_rng(wid)
        n_checked = 0
        try:
            while not stop.is_set() or n_checked < 5:
                Q = _series(4, seed=int(rng.integers(1 << 30)))
                window = None
                if rng.random() < 0.5:
                    t0 = int(rng.integers(0, n_ingest))
                    window = (t0, int(rng.integers(t0, n_ingest)))
                with idx.lsm.registry.pin() as snap:
                    vals, gids, _ = idx.lsm.knn_batch(
                        Q, k=5, raw=idx.raw, window=window, snapshot=snap)
                    bv, _ = _snapshot_bruteforce(snap, X_all, window, Q, 5)
                # distances must match brute force over the pinned epoch
                np.testing.assert_allclose(vals, bv, rtol=1e-5, atol=1e-4)
                # every returned id must come from the snapshot and carry
                # its true exact distance (no phantom/stale entries)
                for qi in range(len(Q)):
                    for vj, gj in zip(vals[qi], gids[qi]):
                        if gj < 0:
                            continue
                        true = float(((X_all[gj] - Q[qi]).astype(np.float64)
                                      ** 2).sum())
                        assert abs(true - float(vj)) <= 1e-4 + 1e-5 * true
                n_checked += 1
        except Exception as e:  # noqa: BLE001 - surfaced on the main thread
            errors.append(e)

    threads = [threading.Thread(target=worker, args=(w,)) for w in range(4)]
    for t in threads:
        t.start()
    for b in range(n_ingest):
        idx.ingest(X_parts[b], np.full(bsz, b, np.int64))
    idx.drain(timeout=300)
    stop.set()
    for t in threads:
        t.join(timeout=120)
    idx.close()
    assert not errors, errors[0]
    assert idx.lsm.n_merges > 0  # the race actually exercised merges


def test_no_arena_released_while_pinned_end_to_end():
    """Materialized runs own device arenas; a merge must not release a
    victim's arena while an older epoch is pinned."""
    from repro.core.verify_engine import get_engine

    eng = get_engine()
    raw = RawStore(64)
    lsm = CLSM(CLSMConfig(summarization=CFG, buffer_entries=64,
                          growth_factor=2, block_size=32, materialized=True,
                          merge=False), disk=raw.disk)
    for i in range(2):
        x = _series(64, seed=300 + i)
        lsm.insert(x, raw.append(x), np.full(64, i, np.int64))
    runs = lsm.registry.current().runs_newest_first()
    assert len(runs) == 2
    for r in runs:
        r.device_view()  # force the arenas into existence
    lsm.cfg.merge = True
    before = eng.stats["released_arenas"]
    with lsm.registry.pin():
        lsm._maybe_merge(0)
        assert lsm.registry.retired_pending == 2
        assert eng.stats["released_arenas"] == before  # pinned: kept warm
    assert lsm.registry.retired_pending == 0
    assert eng.stats["released_arenas"] == before + 2
