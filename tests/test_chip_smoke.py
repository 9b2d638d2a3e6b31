"""``chip_smoke.py`` rehearsed on the CPU at a tiny size: its phases run
here with the kernels in interpret mode, and its ``main`` refuses to run
without a TPU."""
import importlib.util
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.join(os.path.dirname(__file__), "..")


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_main_refuses_cpu(smoke, capsys):
    assert smoke.main([]) != 0
    out = capsys.readouterr()
    assert out.out == ""  # no result line
    assert "no TPU found" in out.err


def test_brute_force_matches_full_sort(smoke):
    batches = smoke.generate(700, seed=3, batch=100)
    Q = smoke.queries(5, seed=3)
    X = np.concatenate(batches).astype(np.float64)
    for window, lo, hi in ((None, 0, 700), ((2, 4), 200, 500)):
        vals, ids = smoke.brute_force(batches, Q, 7, window=window, chunk=64)
        for q, v, i in zip(Q.astype(np.float64), vals, ids):
            d2 = ((X[lo:hi] - q) ** 2).sum(axis=1)
            want = lo + np.argsort(d2, kind="stable")[:7]
            np.testing.assert_array_equal(i, want)
            np.testing.assert_allclose(v, d2[want - lo], rtol=1e-12)


def test_kernel_phase_in_interpret_mode(smoke):
    smoke.check_kernels(shapes=[(8, 640, 128, 9), (16, 256, 256, 18)])


def test_slate_check_allows_only_near_tie_swaps(smoke):
    tie = np.nextafter(np.float32(2), np.float32(3))  # one ulp above 2
    full_v = np.array([[1, 2, tie, 3, 4, 5]], np.float32)
    full_i = np.arange(6, dtype=np.int32)[None]
    qn2, xn2 = np.ones(1, np.float32), np.ones(6, np.float32)
    v = full_v[:, :3]
    swapped, gap = smoke.check_slate(v, np.array([[0, 2, 1]]), full_v, full_i,
                                     qn2, xn2, "near tie")
    assert swapped == 2 and 0 < gap <= 1
    for bad in ([[0, 1, 3]], [[0, 1, 1]]):  # a real misrank; a repeat
        with pytest.raises(AssertionError):
            smoke.check_slate(v, np.array(bad), full_v, full_i, qn2, xn2,
                              "bad slate")


def test_host_memory_available_is_positive(smoke):
    avail, source = smoke.host_memory_available()
    assert avail > 0 and source


def test_serve_phase_at_tiny_size(smoke):
    batches = smoke.generate(6000, seed=0, batch=600)
    idx = smoke.build_index(batches)
    try:
        report = smoke.serve_and_check(idx, batches, 48, seed=0)
    finally:
        idx.close()
    assert report["calls"] > 0
    assert report["served"]["exact"] and report["served"]["approx"]


MESH_SCRIPT = r"""
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import importlib.util
spec = importlib.util.spec_from_file_location("chip_smoke", sys.argv[1])
smoke = importlib.util.module_from_spec(spec)
spec.loader.exec_module(smoke)
batches = smoke.generate(12000, seed=1, batch=1200)
idx = smoke.build_index(batches)
try:
    smoke.mesh_check(idx, batches, 12, seed=1)
finally:
    idx.close()
print("MESH_SMOKE_OK")
"""


def test_mesh_phase_on_four_cpu_devices():
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    env.pop("XLA_FLAGS", None)
    r = subprocess.run(
        [sys.executable, "-c", MESH_SCRIPT, os.path.join(REPO, "chip_smoke.py")],
        env=env, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "MESH_SMOKE_OK" in r.stdout
    assert "device 3 holds" in r.stdout  # the shards really are spread
