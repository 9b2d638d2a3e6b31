"""Mesh-sharded batch serving (queries x runs 2-D shard_map) on 8 CPU
devices: the executor's ``shard="mesh"`` mode must match the single-device
engine exactly, and must compose with the sample-sorted distributed build.

Runs in a subprocess because jax pins the device count at first init.
"""
import os
import subprocess
import sys

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import AxisType
from repro.core import (CTree, CTreeConfig, RawStore, StreamConfig,
                        StreamingIndex, SummarizationConfig, ed2)
from repro.core.distributed import (DistBuildConfig, default_batch_mesh,
                                    make_build_fn, mesh_topk_candidates,
                                    valid_entries)
from repro.core.execute import _rerank_slate

mesh = default_batch_mesh()
assert dict(zip(mesh.axis_names, mesh.devices.shape)) == {"q": 2, "r": 4}

CFG = SummarizationConfig(series_len=64, n_segments=8, card_bits=6)
rng = np.random.default_rng(0)

# --- 1. CTree: mesh answers == single-device answers, exactly -------------
X = rng.standard_normal((3000, 64)).astype(np.float32).cumsum(axis=1)
Q = rng.standard_normal((13, 64)).astype(np.float32).cumsum(axis=1)
raw = RawStore(64)
ids = raw.append(X)
ct = CTree(CTreeConfig(summarization=CFG, block_size=256, materialized=True))
ct.bulk_build(X, ids)
v1, g1, _ = ct.knn_batch(Q, k=5, raw=raw)
v2, g2, _ = ct.knn_batch(Q, k=5, raw=raw, shard="mesh")
np.testing.assert_array_equal(g1, g2)
np.testing.assert_array_equal(v1, v2)

# --- 2. streaming window query over many live runs (batch not divisible
#        by the q axis; k slots partially unfillable) ----------------------
idx = StreamingIndex(StreamConfig(scheme="BTP", summarization=CFG,
                                  buffer_entries=512, growth_factor=3,
                                  block_size=128))
for b in range(10):
    x = rng.standard_normal((300, 64)).astype(np.float32).cumsum(axis=1)
    idx.ingest(x, np.full(300, b, np.int64))
Qw = rng.standard_normal((7, 64)).astype(np.float32).cumsum(axis=1)
v1, g1, _ = idx.window_knn_batch(Qw, 2, 8, k=4)
v2, g2, _ = idx.window_knn_batch(Qw, 2, 8, k=4, shard="mesh")
np.testing.assert_array_equal(g1, g2)
np.testing.assert_array_equal(v1, v2)

# --- 2b. adversarial conditioning: large common offset + near-ties, where
#         an uncertified f32 screen would mis-rank — the certification +
#         host-exact fallback must keep mesh ids AND distances identical --
Xa = (3000.0 + 0.01 * rng.standard_normal((3000, 64))).astype(np.float32)
rawa = RawStore(64)
cta = CTree(CTreeConfig(summarization=CFG, block_size=256, materialized=True))
cta.bulk_build(Xa, rawa.append(Xa))
Qa = Xa[rng.integers(0, 3000, 9)] + 0.001 * rng.standard_normal((9, 64)).astype(np.float32)
v1, g1, _ = cta.knn_batch(Qa, k=5, raw=rawa)
v2, g2, _ = cta.knn_batch(Qa, k=5, raw=rawa, shard="mesh")
np.testing.assert_array_equal(g1, g2)
np.testing.assert_array_equal(v1, v2)
for i in range(9):
    bf = np.sort(ed2(Qa[i].astype(np.float64), Xa.astype(np.float64)))[:5]
    np.testing.assert_allclose(v1[i], bf, rtol=1e-5)

# --- 3. composes with the sample-sorted distributed build -----------------
mesh1d = jax.make_mesh((8,), ("data",), axis_types=(AxisType.Auto,))
dcfg = DistBuildConfig(summarization=SummarizationConfig(64, 8, 8),
                       capacity_slack=3.0)
N = 8 * 256
Xd = rng.standard_normal((N, 64)).astype(np.float32).cumsum(axis=1)
idxd = make_build_fn(mesh1d, ("data",), dcfg)(
    jnp.asarray(Xd), jnp.asarray(np.arange(N, dtype=np.int32)))
series, gids = valid_entries(idxd)
assert series.shape[0] == N
Qd = rng.standard_normal((5, 64)).astype(np.float32).cumsum(axis=1)
_, rows = mesh_topk_candidates(Qd, series, 5 + 8)
nv, nrows = _rerank_slate(Qd, series, rows, 5)
for i in range(5):
    bf = np.sort(ed2(Qd[i], Xd))[:5]
    np.testing.assert_allclose(nv[i], bf, rtol=1e-6)
    np.testing.assert_allclose(np.sort(ed2(Qd[i], Xd[gids[nrows[i]]])), bf,
                               rtol=1e-6)
print("MESH_BATCH_OK")
"""


@pytest.mark.slow
def test_mesh_sharded_batch_matches_single_device_8dev():
    env = dict(os.environ, PYTHONPATH=SRC)
    env.pop("XLA_FLAGS", None)
    r = subprocess.run(
        [sys.executable, "-c", SCRIPT], env=env, capture_output=True, text=True,
        timeout=420,
    )
    assert r.returncode == 0, r.stderr[-2000:]
    assert "MESH_BATCH_OK" in r.stdout
