"""Distributed Coconut (shard_map sample-sort + query) on 8 CPU devices.

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
from repro.core import SummarizationConfig, ed2
from repro.core.distributed import DistBuildConfig, make_build_fn, make_query_fn

mesh = jax.make_mesh((8,), ("data",), axis_types=(AxisType.Auto,))
scfg = SummarizationConfig(series_len=64, n_segments=8, card_bits=8)
cfg = DistBuildConfig(summarization=scfg, capacity_slack=3.0)
rng = np.random.default_rng(0)
N = 8 * 256
X = rng.standard_normal((N, 64)).astype(np.float32).cumsum(axis=1)
ids = np.arange(N, dtype=np.int32)
build = make_build_fn(mesh, ("data",), cfg)
idx = build(jnp.asarray(X), jnp.asarray(ids))
assert int(idx["overflow"]) == 0, "bucket overflow"
keys = np.asarray(idx["keys"]); inval = np.asarray(idx["invalid"])
assert int(np.asarray(idx["n_valid"]).sum()) == N
valid = [tuple(r) for r in keys[inval == 0]]
assert valid == sorted(valid), "global sort order violated"

query = make_query_fn(mesh, ("data",), cfg, k=5, verify_budget=N)
Q = rng.standard_normal((3, 64)).astype(np.float32).cumsum(axis=1)
d2, qids = query(idx, jnp.asarray(Q))
for i in range(3):
    bf = np.sort(ed2(Q[i], X))[:5]
    np.testing.assert_allclose(np.sort(np.asarray(d2)[i]), bf, rtol=1e-4)
# ids must point at the right series
for i in range(3):
    got = np.sort(np.asarray(d2)[i])
    via_ids = np.sort(ed2(Q[i], X[np.asarray(qids)[i]]))
    np.testing.assert_allclose(got, via_ids, rtol=1e-4)
print("DISTRIBUTED_OK")
"""


@pytest.mark.slow
def test_distributed_build_and_query_8dev():
    env = dict(os.environ, PYTHONPATH=SRC)
    env.pop("XLA_FLAGS", None)
    r = subprocess.run(
        [sys.executable, "-c", SCRIPT], env=env, capture_output=True, text=True,
        timeout=420,
    )
    assert r.returncode == 0, r.stderr[-2000:]
    assert "DISTRIBUTED_OK" in r.stdout
