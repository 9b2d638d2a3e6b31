"""Elastic checkpoint restore: save under one mesh shape, restore under
another (scale up), continue training — values preserved exactly.

Subprocess-based (device count pins at first jax init).
"""
import os
import subprocess
import sys

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

SCRIPT = r"""
import os, sys, tempfile
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import AxisType
from repro.train import checkpoint as ckpt

ckdir = tempfile.mkdtemp()

# "cluster A": 4 devices (2x2 mesh), params sharded (data, model)
mesh_a = jax.make_mesh((2, 2), ("data", "model"), devices=jax.devices()[:4],
                       axis_types=(AxisType.Auto,) * 2)
w = jnp.arange(64 * 64, dtype=jnp.float32).reshape(64, 64)
w_a = jax.device_put(w, NamedSharding(mesh_a, P("data", "model")))
ckpt.save(ckdir, 7, {"w": w_a})

# "cluster B": all 8 devices (8x1), different sharding
mesh_b = jax.make_mesh((8,), ("data",), axis_types=(AxisType.Auto,))
sh_b = {"w": NamedSharding(mesh_b, P("data", None))}
like = {"w": jax.ShapeDtypeStruct((64, 64), jnp.float32)}
restored, _ = ckpt.restore(ckdir, 7, like, shardings=sh_b)
assert restored["w"].sharding.mesh.devices.size == 8
np.testing.assert_array_equal(np.asarray(restored["w"]), np.asarray(w))
# restored array is usable in computation under the new mesh
out = jax.jit(lambda x: (x @ x.T).sum())(restored["w"])
assert np.isfinite(float(out))
print("ELASTIC_OK")
"""


@pytest.mark.slow
def test_elastic_restore_across_mesh_shapes():
    env = dict(os.environ, PYTHONPATH=SRC)
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "ELASTIC_OK" in r.stdout
