"""The SLO-aware dynamic-batching gateway: ladder coalescing, deadline
flush, per-request tier routing, SLO shedding with hysteresis, provenance —
and the acceptance contract: gateway answers are bitwise-identical to
direct ``knn_batch`` calls at the same tier (padding never leaks), with
pinned-epoch semantics per formed batch, under concurrent clients and a
background-ingest stream."""
import threading
import time

import numpy as np
import pytest

from repro.core import (Gateway, GatewayConfig, StreamConfig, StreamingIndex,
                        SummarizationConfig)
from repro.core.gateway import ladder
from repro.core.verify_engine import get_engine

LEN = 64
CFG = SummarizationConfig(series_len=LEN, n_segments=8, card_bits=6)


def _series(n, seed):
    rng = np.random.default_rng(seed)
    return rng.standard_normal((n, LEN)).astype(np.float32).cumsum(axis=1)


def _index(n_batches=8, bsz=300, **kw):
    idx = StreamingIndex(StreamConfig(scheme="BTP", summarization=CFG,
                                      buffer_entries=512, growth_factor=3,
                                      block_size=128, **kw))
    for b in range(n_batches):
        idx.ingest(_series(bsz, 100 + b), np.full(bsz, b, np.int64))
    return idx


@pytest.fixture(scope="module")
def idx():
    return _index()


def _gateway(idx, **kw):
    kw.setdefault("deadline_ms", 3.0)
    kw.setdefault("max_batch", 16)
    kw.setdefault("k", 5)
    return Gateway(idx, GatewayConfig(**kw))


# --------------------------------------------------------------- unit tier
def test_ladder_rungs_are_engine_batch_buckets():
    assert ladder(64) == (8, 16, 32, 64)
    assert ladder(16) == (8, 16)
    assert ladder(8) == (8,)
    # a non-bucket max still tops the ladder (the engine pads past it)
    assert ladder(24) == (8, 16, 24)


def test_max_batch_cannot_exceed_engine_chunk(idx):
    with pytest.raises(ValueError):
        Gateway(idx, GatewayConfig(max_batch=128))


def test_single_request_deadline_flush(idx):
    gw = _gateway(idx, deadline_ms=5.0)
    try:
        r = gw.submit(_series(1, 7)[0]).result(timeout=30)
        assert r.batch_size == 1
        assert r.padded_to == 8  # padded up to the rung floor
        assert r.tier_served == "exact" and not r.shed
        assert r.ids.shape == (5,)
        st = gw.snapshot_stats()
        assert st["deadline_flushes"] == 1 and st["full_flushes"] == 0
        assert st["batch_hist"] == {1: 1}
    finally:
        gw.close()


def test_full_rung_flushes_without_waiting_deadline(idx):
    # a long deadline: only a full top rung can flush this fast
    gw = _gateway(idx, deadline_ms=2_000.0, max_batch=8)
    try:
        Q = _series(8, 8)
        t0 = time.perf_counter()
        tix = [gw.submit(q) for q in Q]
        resps = [t.result(timeout=60) for t in tix]
        assert (time.perf_counter() - t0) < 100.0  # not the 2s deadline
        assert all(r.batch_size == 8 and r.padded_to == 8 for r in resps)
        assert gw.snapshot_stats()["full_flushes"] >= 1
    finally:
        gw.close()


def test_padding_never_leaks_parity_all_rungs(idx):
    """Every partial-batch size pads to its rung; answers must be bitwise
    equal to a direct call with ONLY the real queries."""
    gw = _gateway(idx, deadline_ms=2.0)
    try:
        for m in (1, 3, 5, 9, 13):
            Q = _series(m, 200 + m)
            resps = [t.result(timeout=60) for t in
                     [gw.submit(q) for q in Q]]
            vals, gids, _ = idx.knn_batch(Q, k=5)
            for i, r in enumerate(resps):
                assert np.array_equal(r.ids, gids[i])
                assert np.array_equal(r.vals, vals[i])
    finally:
        gw.close()


def test_mixed_tier_batch_splits_and_matches_direct_calls(idx):
    """One formed batch carrying exact + approx + windowed requests splits
    into per-(tier, n_blocks, k, window) sub-batches; each answer matches
    the direct batched call at the same tier bitwise."""
    gw = _gateway(idx, deadline_ms=20.0, max_batch=16)
    try:
        Q = _series(12, 31)
        tix = []
        for i in range(4):  # plain exact, whole history
            tix.append(gw.submit(Q[i]))
        for i in range(4, 8):  # recall-targeted -> approx tier
            tix.append(gw.submit(Q[i], target_recall=0.9))
        for i in range(8, 12):  # windowed exact
            tix.append(gw.submit(Q[i], window=(2, 6)))
        resps = [t.result(timeout=60) for t in tix]
        epochs = {r.epoch for r in resps}
        assert len(epochs) == 1  # ONE pinned epoch per formed batch
        assert all(r.batch_size == 12 for r in resps)
        ev, ei, _ = idx.knn_batch(Q[:4], k=5)
        nb = resps[4].n_blocks
        av, ai, _ = idx.knn_approx_batch(Q[4:8], k=5, n_blocks=nb)
        wv, wi, _ = idx.window_knn_batch(Q[8:12], 2, 6, k=5)
        for i in range(4):
            assert resps[i].tier_served == "exact"
            assert np.array_equal(resps[i].ids, ei[i])
            assert np.array_equal(resps[i].vals, ev[i])
            assert resps[4 + i].tier_served == "approx"
            assert np.array_equal(resps[4 + i].ids, ai[i])
            assert np.array_equal(resps[4 + i].vals, av[i])
            assert resps[8 + i].tier_served == "exact"
            assert np.array_equal(resps[8 + i].ids, wi[i])
            assert np.array_equal(resps[8 + i].vals, wv[i])
    finally:
        gw.close()


def test_deterministic_mixed_tenant_split(idx):
    """The same mixed-tenant submission (half strict-recall, half
    tight-latency) must route and split identically on every run."""
    def run_once():
        gw = _gateway(idx, deadline_ms=20.0, max_batch=16)
        try:
            Q = _series(8, 77)
            tix = []
            for i in range(4):
                tix.append(gw.submit(Q[i], target_recall=1.0))
            for i in range(4, 8):
                tix.append(gw.submit(Q[i], target_recall=0.9,
                                     latency_budget_ms=0.05))
            rs = [t.result(timeout=60) for t in tix]
            return [(r.tier_served, r.n_blocks, r.shed, r.conflict,
                     tuple(r.ids)) for r in rs]
        finally:
            gw.close()

    a, b = run_once(), run_once()
    assert a == b
    # strict-recall half stays exact and is never shed/conflicted
    assert all(t == ("exact",) + t[1:] and not t[2] and not t[3]
               for t in a[:4])
    # tight-latency half: capped n_blocks -> conflict -> marked shed
    assert all(t[0] == "approx" and t[2] and t[3] for t in a[4:])


def test_conflict_propagates_into_shed_decision(idx):
    """The recommender's 'latency cap makes the recall target unreachable'
    verdict must arrive as a structured flag and mark the answer shed even
    with no SLO pressure."""
    gw = _gateway(idx, slo_p99_ms=1e9)  # never under pressure
    try:
        r = gw.submit(_series(1, 5)[0], target_recall=0.95,
                      latency_budget_ms=0.05).result(timeout=30)
        assert r.conflict and r.shed and r.tier_served == "approx"
        ok = gw.submit(_series(1, 6)[0], target_recall=0.9).result(timeout=30)
        assert not ok.conflict and not ok.shed
    finally:
        gw.close()


def test_slo_shedding_engages_and_spares_strict_requests(idx):
    """With an impossible SLO the rolling p99 trips immediately: sheddable
    exact traffic downgrades to approx with shed provenance; strict
    (target_recall >= 1.0) requests keep the exact tier."""
    gw = _gateway(idx, slo_p99_ms=0.001, min_shed_samples=8,
                  deadline_ms=1.0, max_batch=8)
    try:
        Q = _series(40, 50)
        # prime the rolling window past min_shed_samples
        for i in range(16):
            gw.submit(Q[i]).result(timeout=30)
        assert gw.snapshot_stats()["shedding"]
        shed = gw.submit(Q[20]).result(timeout=30)
        assert shed.shed and shed.tier_served == "approx"
        assert shed.n_blocks == gw.cfg.shed_n_blocks
        strict = gw.submit(Q[21], target_recall=1.0).result(timeout=30)
        assert not strict.shed and strict.tier_served == "exact"
        # shed answers still match the direct approx call bitwise
        av, ai, _ = idx.knn_approx_batch(Q[20:21], k=5,
                                         n_blocks=shed.n_blocks)
        assert np.array_equal(shed.ids, ai[0])
        st = gw.snapshot_stats()
        assert st["shed_transitions"] >= 1 and st["shed_served"] >= 1
    finally:
        gw.close()


def test_shed_hysteresis_recovers():
    """Shedding must exit once the rolling p99 falls below the exit
    fraction of the SLO — exercised directly against the update rule."""
    idx2 = _index(n_batches=2, bsz=100)
    gw = _gateway(idx2, slo_p99_ms=50.0, min_shed_samples=4)
    try:
        with gw._cond:
            gw._lat_ms.extend([100.0] * 8)
            gw._update_shed_locked()
            assert gw._shedding
            gw._lat_ms.extend([1.0] * gw.cfg.lat_window)  # window rolls over
            gw._update_shed_locked()
            assert not gw._shedding
            assert gw.stats["shed_transitions"] == 2
    finally:
        gw.close()
        idx2.close()


def test_reset_slo_window_clears_shed_state():
    """Harnesses drop the warm-up latencies (one-time compiles) from the
    rolling window before measuring; the reset also leaves the shed state
    and counts as a transition."""
    idx2 = _index(n_batches=2, bsz=100)
    gw = _gateway(idx2, slo_p99_ms=50.0, min_shed_samples=4)
    try:
        with gw._cond:
            gw._lat_ms.extend([100.0] * 8)
            gw._update_shed_locked()
            assert gw._shedding
        gw.reset_slo_window()
        st = gw.snapshot_stats()
        assert not st["shedding"] and st["p99_ms"] == 0.0
        assert st["shed_transitions"] == 2
        gw.reset_slo_window()  # idempotent when not shedding
        assert gw.snapshot_stats()["shed_transitions"] == 2
    finally:
        gw.close()
        idx2.close()


def test_queue_wait_provenance_and_bounded_queue(idx):
    gw = _gateway(idx, deadline_ms=10.0)
    try:
        r = gw.submit(_series(1, 9)[0]).result(timeout=30)
        assert 0.0 <= r.queue_wait_ms <= r.latency_ms
    finally:
        gw.close()
    with pytest.raises(RuntimeError):
        gw.submit(_series(1, 9)[0])  # closed gateway rejects


# ------------------------------------------------------- integration tier
def test_concurrent_clients_with_background_ingest_parity():
    """The acceptance test: concurrent single-query clients against a
    background-ingest stream. During the live phase every response must be
    internally consistent (one pinned epoch per formed batch, monotone
    non-decreasing epochs, valid slates); after ingest quiesces, gateway
    answers must be bitwise-identical to direct batched calls at the same
    tier."""
    idx = _index(n_batches=4, bsz=250, ingest="async")
    gw = _gateway(idx, deadline_ms=4.0, max_batch=16)
    stop = threading.Event()

    def ingester():
        b = 4
        while not stop.is_set() and b < 10:
            idx.ingest(_series(250, 300 + b), np.full(250, b, np.int64))
            b += 1
            time.sleep(0.005)

    results = {}
    errs = []

    def client(cid):
        try:
            rng = np.random.default_rng(1000 + cid)
            out = []
            for j in range(6):
                q = rng.standard_normal(LEN).astype(np.float32).cumsum()
                kw = {}
                if j % 3 == 1:
                    kw["target_recall"] = 0.9
                if j % 2 == 1:
                    kw["window"] = (0, 3)
                out.append((q, kw, gw.submit(q, **kw).result(timeout=120)))
            results[cid] = out
        except BaseException as e:  # noqa: BLE001 — surface in main thread
            errs.append(e)

    ing = threading.Thread(target=ingester)
    clients = [threading.Thread(target=client, args=(c,)) for c in range(6)]
    ing.start()
    for t in clients:
        t.start()
    for t in clients:
        t.join(timeout=180)
    stop.set()
    ing.join(timeout=60)
    try:
        assert not errs, errs
        # live-phase invariants: sorted slates, valid ids, batch-level epochs
        by_batch = {}
        for out in results.values():
            for _, _, r in out:
                assert r.vals.shape == (5,) and r.ids.shape == (5,)
                assert (np.diff(r.vals) >= 0).all()
                assert (r.ids >= 0).all()  # k << live entries: full slates
                by_batch.setdefault((r.epoch, r.batch_size,
                                     round(r.queue_wait_ms, 6)), 0)
        # quiesced phase: ingest drained -> parity must be bitwise. A loaded
        # host can push the live phase's rolling p99 past the SLO, and the
        # shed state would serve the unmarked exact requests approximately
        idx.drain(timeout=120)
        gw.reset_slo_window()
        Q = _series(10, 999)
        resps = [t.result(timeout=60) for t in
                 [gw.submit(q) for q in Q[:5]] +
                 [gw.submit(q, target_recall=0.9) for q in Q[5:]]]
        ev, ei, _ = idx.knn_batch(Q[:5], k=5)
        nb = resps[5].n_blocks
        av, ai, _ = idx.knn_approx_batch(Q[5:], k=5, n_blocks=nb)
        for i in range(5):
            assert np.array_equal(resps[i].ids, ei[i])
            assert np.array_equal(resps[i].vals, ev[i])
            assert np.array_equal(resps[5 + i].ids, ai[i])
            assert np.array_equal(resps[5 + i].vals, av[i])
    finally:
        gw.close()
        idx.close()


def test_prewarmed_gateway_serves_with_zero_retraces():
    """After ``Gateway.prewarm`` covers the stream's table sizes, serving
    across every rung — including deadline-flushed padded batches — must
    not retrace."""
    idx = _index(n_batches=6, bsz=400)
    gw = _gateway(idx, deadline_ms=2.0, max_batch=16)
    engine = get_engine()
    try:
        gw.prewarm([400 * (b + 1) for b in range(6)])
        before = engine.stats["traces"]
        for m in (1, 4, 8, 11, 16):
            Q = _series(m, 600 + m)
            for t in [gw.submit(q) for q in Q]:
                t.result(timeout=60)
        assert engine.stats["traces"] == before
        # the engine-side served-batch histogram moved (monotonic counter)
        assert sum(engine.stats["batch_hist"].values()) > 0
    finally:
        gw.close()
        idx.close()


def test_engine_batch_hist_is_monotonic(idx):
    engine = get_engine()
    h0 = dict(engine.stats["batch_hist"])
    vals, gids, _ = idx.knn_batch(_series(16, 42), k=5)
    h1 = dict(engine.stats["batch_hist"])
    assert all(h1.get(kk, 0) >= v for kk, v in h0.items())
    assert sum(h1.values()) >= sum(h0.values())


# ------------------------------------------------------------ typed stats
def test_snapshot_is_typed_and_dict_view_matches(idx):
    """snapshot() returns the frozen GatewayStats; snapshot_stats() is its
    exact dict rendering (the old surface, kept for log emitters)."""
    import dataclasses as dc

    from repro.core import GatewayStats

    gw = _gateway(idx)
    try:
        for i in range(5):
            gw.submit(_series(1, 40 + i)[0]).result(timeout=60)
        snap = gw.snapshot()
        assert isinstance(snap, GatewayStats)
        assert dc.asdict(gw.snapshot()) == gw.snapshot_stats()
        assert snap.served == 5 and snap.submitted == 5
        assert not snap.autotune and snap.tuner_decisions == 0
        with pytest.raises(dc.FrozenInstanceError):
            snap.served = 0
        # the dict view keeps the pre-redesign key set (+ the tuner block)
        keys = set(gw.snapshot_stats())
        assert {"served", "submitted", "batches", "queue_depth", "shedding",
                "p50_ms", "p99_ms", "batch_hist", "autotune"} <= keys
    finally:
        gw.close()
