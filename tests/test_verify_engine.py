"""The device-resident verification engine (the executor's default
backend): bitwise parity with the retained host path across every index x
tier x scalar/batch on well-conditioned data, error-bound-certified
exactness on adversarially conditioned data, the steady-state zero-retrace
guarantee of the shape-bucketed compile cache, and the arena lifecycle
(one upload per table, in-place extends for append-only stores)."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    ADSConfig,
    ADSIndex,
    CLSM,
    CLSMConfig,
    CTree,
    CTreeConfig,
    RawStore,
    StreamConfig,
    StreamingIndex,
    SummarizationConfig,
    ed2,
)
from repro.core.verify_engine import (
    VerifyEngine,
    _bucket_batch,
    _bucket_rows,
    _screen_core,
    get_engine,
)
from repro.kernels import ops as kops

CFG = SummarizationConfig(series_len=64, n_segments=8, card_bits=6)


def _data(n=6000, seed=0):
    rng = np.random.default_rng(seed)
    return rng.standard_normal((n, 64)).astype(np.float32).cumsum(axis=1)


def _queries(m=32, seed=99):
    rng = np.random.default_rng(seed)
    return rng.standard_normal((m, 64)).astype(np.float32).cumsum(axis=1)


def _adversarial(n, seed=0, offset=3000.0, spread=0.01):
    """Large common offset + tiny relative distances: the f32
    |q|^2 + |x|^2 - 2<q, x> cancellation trap (PR 3's hardening suite)."""
    rng = np.random.default_rng(seed)
    return (offset + spread * rng.standard_normal((n, 64))).astype(np.float32)


def _ctree(mat, X, raw):
    ct = CTree(CTreeConfig(summarization=CFG, block_size=512,
                           materialized=mat))
    ct.bulk_build(X, raw.append(X))
    return ct


# ---------------------------------------------------------------------------
# device == host, bitwise, on every index x tier x scalar/batch
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("mat", [True, False])
def test_ctree_device_matches_host_bitwise(mat):
    X, Q = _data(), _queries()
    raw = RawStore(64)
    ct = _ctree(mat, X, raw)
    calls0 = get_engine().stats["calls"]
    vd, gd, sd = ct.knn_batch(Q, k=10, raw=raw)  # device is the default
    vn, gn, sn = ct.knn_batch(Q, k=10, raw=raw, backend="numpy")
    np.testing.assert_array_equal(vd, vn)
    np.testing.assert_array_equal(gd, gn)
    # identical pruning accounting too — the device pass is a drop-in
    assert (sd.entries_verified, sd.blocks_visited) == (
        sn.entries_verified, sn.blocks_visited)
    assert get_engine().stats["calls"] > calls0  # device actually engaged
    # approximate tier
    va, ga, _ = ct.knn_approx_batch(Q, k=10, n_blocks=3, raw=raw)
    vb, gb, _ = ct.knn_approx_batch(Q, k=10, n_blocks=3, raw=raw,
                                    backend="numpy")
    np.testing.assert_array_equal(va, vb)
    np.testing.assert_array_equal(ga, gb)


def test_ctree_scalar_is_batch_of_one_on_device():
    X, Q = _data(3000, seed=2), _queries(1, seed=5)
    raw = RawStore(64)
    ct = _ctree(True, X, raw)
    res, _ = ct.knn_exact(Q[0], k=5, raw=raw)
    vals, gids, _ = ct.knn_batch(Q, k=5, raw=raw)
    assert [d for d, _ in res] == [float(v) for v in vals[0]]
    assert [g for _, g in res] == [int(g) for g in gids[0]]


def test_clsm_device_matches_host_bitwise():
    X, Q = _data(5000, seed=3), _queries(24, seed=7)
    raw = RawStore(64)
    lsm = CLSM(CLSMConfig(summarization=CFG, buffer_entries=1024,
                          growth_factor=3, block_size=256, materialized=True))
    lsm.insert(X, raw.append(X), np.arange(len(X), dtype=np.int64))
    vd, gd, _ = lsm.knn_batch(Q, k=7, raw=raw)
    vn, gn, _ = lsm.knn_batch(Q, k=7, raw=raw, backend="numpy")
    np.testing.assert_array_equal(vd, vn)
    np.testing.assert_array_equal(gd, gn)


@pytest.mark.parametrize("mode", ["full", "adaptive"])
def test_ads_device_matches_host_bitwise(mode):
    X, Q = _data(4000, seed=4), _queries(16, seed=9)
    raw = RawStore(64)
    ids = raw.append(X)

    def build():
        ads = ADSIndex(ADSConfig(summarization=CFG, leaf_size=2048,
                                 mode=mode, query_leaf_size=256))
        ads.insert_batch(X, ids)
        return ads

    # adaptive splits mutate the tree during queries, so each backend gets
    # a fresh build (same data -> same refinement decisions)
    vd, gd, _ = build().knn_batch(Q, k=5, raw=raw)
    vn, gn, _ = build().knn_batch(Q, k=5, raw=raw, backend="numpy")
    np.testing.assert_array_equal(vd, vn)
    np.testing.assert_array_equal(gd, gn)
    ads = build()
    va, ga, _ = ads.knn_approx_batch(Q, k=5, raw=raw)
    vb, gb, _ = build().knn_approx_batch(Q, k=5, raw=raw, backend="numpy")
    np.testing.assert_array_equal(va, vb)
    np.testing.assert_array_equal(ga, gb)


def test_streaming_window_device_matches_host_bitwise():
    rng = np.random.default_rng(11)
    idx = StreamingIndex(StreamConfig(scheme="BTP", summarization=CFG,
                                      buffer_entries=1024, growth_factor=3,
                                      block_size=256, materialized=False))
    for b in range(8):
        x = rng.standard_normal((600, 64)).astype(np.float32).cumsum(axis=1)
        idx.ingest(x, np.full(600, b, np.int64))
    Q = _queries(16, seed=13)
    vd, gd, _ = idx.window_knn_batch(Q, 2, 6, k=4)
    vn, gn, _ = idx.window_knn_batch(Q, 2, 6, k=4, backend="numpy")
    np.testing.assert_array_equal(vd, vn)
    np.testing.assert_array_equal(gd, gn)


def test_approx_tier_shared_span_group_takes_device_path():
    """Queries that seek into the same neighborhood share one span group —
    the case where the approximate tier's verification clears the device
    floors. Answers must still match the host path bitwise."""
    X = _data(8000, seed=6)
    raw = RawStore(64)
    ct = _ctree(True, X, raw)
    q = _queries(1, seed=17)
    Q = np.repeat(q, 16, axis=0)  # one shared span, 16-query group
    calls0 = get_engine().stats["calls"]
    vd, gd, _ = ct.knn_approx_batch(Q, k=5, n_blocks=4, raw=raw)
    assert get_engine().stats["calls"] > calls0
    vn, gn, _ = ct.knn_approx_batch(Q, k=5, n_blocks=4, raw=raw,
                                    backend="numpy")
    np.testing.assert_array_equal(vd, vn)
    np.testing.assert_array_equal(gd, gn)


# ---------------------------------------------------------------------------
# adversarial conditioning: the certificate keeps the device path exact
# ---------------------------------------------------------------------------
def test_device_exact_under_f32_cancellation():
    X = _adversarial(4000)
    rng = np.random.default_rng(1)
    Q = np.stack([X[i] + 0.001 * rng.standard_normal(64).astype(np.float32)
                  for i in range(16)])
    raw = RawStore(64)
    ct = _ctree(True, X, raw)
    vals, gids, _ = ct.knn_batch(Q, k=5, raw=raw)
    X64 = X.astype(np.float64)
    for i in range(len(Q)):
        bf = ed2(Q[i].astype(np.float64), X64)  # (n,) exact oracle
        want = np.sort(bf)[:5]
        np.testing.assert_allclose(vals[i], want, rtol=1e-5)
        np.testing.assert_allclose(np.sort(bf[gids[i]]), want, rtol=1e-12)


# ---------------------------------------------------------------------------
# steady state: zero retraces after warm-up
# ---------------------------------------------------------------------------
def test_steady_state_serving_never_retraces():
    rng = np.random.default_rng(21)
    idx = StreamingIndex(StreamConfig(scheme="BTP", summarization=CFG,
                                      buffer_entries=2048, growth_factor=4,
                                      block_size=512))
    for b in range(6):
        x = rng.standard_normal((1500, 64)).astype(np.float32).cumsum(axis=1)
        idx.ingest(x, np.full(1500, b, np.int64))
    eng = get_engine()
    # warm up the way serving does: pre-compile the bucket ladder for the
    # store's arena capacity, then one live batch
    eng.prewarm(64, m=16, k=5, caps=[idx.raw.n])
    idx.knn_batch(_queries(16, seed=0), k=5)
    traces0 = eng.stats["traces"]
    calls0 = eng.stats["calls"]
    hits0 = eng.stats["hits"]
    for b in range(10):  # 10 serving batches, varying content + batch size
        m = 16 if b % 2 else 13
        idx.knn_batch(_queries(m, seed=100 + b), k=5)
    # deltas, not the raw counters: the engine is a process singleton, so
    # earlier tests' prewarms (traces without calls) live in the totals
    d_calls = eng.stats["calls"] - calls0
    assert d_calls > 0  # the device path served them
    assert eng.stats["traces"] == traces0  # ...from cached traces only
    assert eng.stats["hits"] - hits0 >= d_calls > 0


def test_prewarm_compiles_the_ladder_once():
    eng = get_engine()
    compiled = eng.prewarm(96, m=16, k=5, caps=[3000])
    again = eng.prewarm(96, m=16, k=5, caps=[3000])
    assert again == 0  # everything already compiled
    assert compiled >= 0  # first call may share traces with earlier tests


# ---------------------------------------------------------------------------
# the packed slate: one download a pass, the same bits as three
# ---------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("s", "full"))
def _three_output_pass(table, xn2, scale, idx, qc, s, full):
    """The verification pass as it was before the slate was packed:
    values, rows and the invalid flags as three separate outputs."""
    if full:
        vals, pidx = _screen_core(
            table, jnp.where(idx, xn2, kops.BIG_NORM2), qc, s, scale)
        return vals, pidx, pidx < 0
    sc = None if scale is None else jnp.take(scale, idx)
    vals, pidx = _screen_core(jnp.take(table, idx, axis=0),
                              jnp.take(xn2, idx), qc, s, sc)
    return vals, jnp.take(idx, jnp.maximum(pidx, 0)), pidx < 0


def _three_download_launch(view, trows, Qc, s):
    """The slate as ``_launch`` returned it with three downloads."""
    m = Qc.shape[0]
    qpad = np.zeros((_bucket_batch(m), Qc.shape[1]), np.float32)
    qpad[:m] = Qc
    bb = max(_bucket_rows(trows.size), _bucket_rows(s, 8))
    full = bb >= view.cap
    if full:
        idx = np.zeros(view.cap, bool)
        idx[trows] = True
    else:
        idx = np.full(bb, view.n, np.int32)
        idx[: trows.size] = trows
    vals, srows, invalid = _three_output_pass(
        view.table, view.xn2, view.scale, jnp.asarray(idx), jnp.asarray(qpad),
        s=s, full=full)
    vals = np.asarray(vals)[:m]
    srows = np.asarray(srows)[:m].astype(np.int64)
    invalid = np.asarray(invalid)[:m]
    srows = np.where(invalid | (srows >= view.n) | (vals >= 1e29), -1, srows)
    return vals, srows


@pytest.mark.parametrize("dtype", ["f32", "bf16", "int8"])
@pytest.mark.parametrize("variant,n,u", [
    ("gather", 3000, 1500),
    ("full", 3000, 2900),
    ("gather_outsized", 3000, 12),  # s > u: sentinel pad rows surface
    ("full_outsized", 40, 12),  # s > u: masked-out rows surface
])
def test_packed_slate_is_bitwise_the_three_downloads(variant, n, u, dtype):
    X, Q = _data(n, seed=31), _queries(70, seed=37)
    eng = VerifyEngine(dtype=dtype)
    view = eng.build_view(X)
    trows = np.sort(np.random.default_rng(41).choice(n, u, replace=False))
    Qc = Q[:16] - view.mu[None, :]
    s = 18
    gathered0 = eng.stats["gathered_rows"]
    vals, srows = eng._launch(view, trows, Qc, s)
    # the variant the pass took: full coverage screens the whole capacity
    assert (eng.stats["gathered_rows"] - gathered0 == view.cap) == (
        variant.startswith("full"))
    want_vals, want_rows = _three_download_launch(view, trows, Qc, s)
    assert vals.dtype == want_vals.dtype and srows.dtype == want_rows.dtype
    np.testing.assert_array_equal(vals.view(np.int32),
                                  want_vals.view(np.int32))
    np.testing.assert_array_equal(srows, want_rows)
    assert (srows == -1).any() == variant.endswith("outsized")
    assert eng.stats["d2h_copies"] == eng.stats["calls"] == 1
    assert eng.stats["d2h_bytes"] == _bucket_batch(16) * 2 * s * 4
    # one screen_topk of 70 queries verifies in two chunks: one copy each
    calls0, copies0 = eng.stats["calls"], eng.stats["d2h_copies"]
    eng.screen_topk(view, trows, Q, k=10)
    assert eng.stats["calls"] - calls0 == 2
    assert eng.stats["d2h_copies"] - copies0 == eng.stats["calls"] - calls0


# ---------------------------------------------------------------------------
# arena lifecycle
# ---------------------------------------------------------------------------
def test_arena_uploads_once_and_extends_in_place():
    X = _data(3000, seed=8)
    raw = RawStore(64)
    ct = _ctree(False, X, raw)  # non-materialized: verifies via raw arena
    Q = _queries(16, seed=3)
    eng = get_engine()
    ct.knn_batch(Q, k=5, raw=raw)
    up0 = eng.stats["uploads"]
    ct.knn_batch(_queries(16, seed=4), k=5, raw=raw)
    assert eng.stats["uploads"] == up0  # immutable store: no re-upload
    view0 = raw.device_view()
    raw.append(_data(48, seed=12))
    # growth that fits the bucketed capacity: the view extends in place
    # (donated update), keeping the same buffers' capacity
    view1 = raw.device_view()
    assert view1.n == 3048 and view1.cap == view0.cap
    assert eng.stats["uploads"] == up0 + 1
    # growth past the capacity: the arena rebuilds at the next bucket
    raw.append(_data(500, seed=14))
    view2 = raw.device_view()
    assert view2.n == 3548 and view2.cap > view0.cap
    # the original index still answers exactly over its 3000 entries
    q = Q[0]
    res, _ = ct.knn_exact(q, k=3, raw=raw)
    bf = np.sort(ed2(q, X))[:3]
    np.testing.assert_allclose([d for d, _ in res], bf, rtol=1e-5)


def test_device_backend_rejected_names_still_error():
    X = _data(500)
    raw = RawStore(64)
    ct = _ctree(True, X, raw)
    with pytest.raises(ValueError, match="backend"):
        ct.knn_batch(_queries(2), k=3, raw=raw, backend="cuda")
