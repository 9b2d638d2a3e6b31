"""The plain reference against a full sort, and its bfloat16 control."""
import numpy as np

from bench import reference as ref


def _data():
    rng = np.random.default_rng(5)
    batches = [rng.standard_normal((50, 32)).astype(np.float32).cumsum(1)
               for _ in range(6)]
    Q = rng.standard_normal((7, 32)).astype(np.float32).cumsum(1)
    return batches, Q


def test_knn_matches_a_full_sort_per_window():
    batches, Q = _data()
    X = np.concatenate(batches).astype(np.float64)
    windows = [None, (2, 4), (0, 0), (5, 5), None, (1, 3), (3, 5)]
    vals, ids = ref.knn(batches, Q, 5, windows, chunk=16)
    for q, v, i, w in zip(Q.astype(np.float64), vals, ids, windows):
        lo, hi = (0, 300) if w is None else (50 * w[0], 50 * (w[1] + 1))
        d2 = ((X[lo:hi] - q) ** 2).sum(axis=1)
        want = lo + np.argsort(d2, kind="stable")[:5]
        np.testing.assert_array_equal(i, want)
        np.testing.assert_allclose(v, d2[want - lo], rtol=1e-12)


def test_distances_and_checks():
    batches, Q = _data()
    vals, ids = ref.knn(batches, Q, 4)
    np.testing.assert_allclose(ref.distances(batches, Q, ids), vals)
    assert ref.id_mismatches(ids, ids) == 0
    assert ref.dist_rel_err(vals.astype(np.float32), vals) < 1e-7
    assert ref.bad_answers(ids, [None] * len(Q), batches, 4) == 0
    dup = ids.copy()
    dup[0, 1] = dup[0, 0]
    assert ref.bad_answers(dup, [None] * len(Q), batches, 4) == 1
    assert ref.bad_answers(ids, [(0, 0)] * len(Q), batches, 4) >= 1
    assert ref.recall(ids, ids) == 1.0


def test_bf16_control_fails_the_limits_f32_passes():
    """The control, the reference on bfloat16-rounded data put in the
    system's place, fails the configuration's limits; the f32 distances a
    sound system returns pass them."""
    from bench import run

    lim = run.load_json(run.ROOT / "bench/configs/rw256-4m.json")["limits"]
    rng = np.random.default_rng(11)
    batches = [rng.standard_normal((300, 256)).astype(np.float32).cumsum(1)
               for _ in range(4)]
    Q = rng.standard_normal((16, 256)).astype(np.float32).cumsum(1)
    vals, ids = ref.knn(batches, Q, 10)
    assert ref.dist_rel_err(vals.astype(np.float32), vals) \
        <= lim["dist_rel_err"]
    cv, ci = ref.knn(batches, Q, 10, precision="bf16")
    assert ref.dist_rel_err(cv, ref.distances(batches, Q, ci)) \
        > lim["dist_rel_err"]
    assert ref.id_mismatches(ci, ids) > lim["id_mismatch"]
