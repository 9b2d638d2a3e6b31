"""Ahead-of-time compiles of the served kernels for a described TPU v5e.

The rest of the suite runs the Pallas kernels in interpret mode on the CPU,
which never asks the TPU compiler whether it accepts them. These tests
compile, with no chip attached, the verification engine's fused passes
through their TPU branch, the PAA kernel and the mesh top-k for a
``v5e:2x2`` topology, so an edit the TPU compiler refuses fails here.
Nothing runs: ``chip_smoke.py`` checks the results on the chip.

The topology is described in a module fixture, never at import: only one
process at a time may load the TPU compiler's library.
"""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import (AxisType, Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

D = 256  # the served series length
CAP = 1 << 22  # a device-resident arena: 2^22 rows
S = 50 + 8  # slate width k + slack for the widest served k
DTYPES = {"f32": jnp.float32, "bf16": jnp.bfloat16, "int8": jnp.int8}


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # no compiler logs in /tmp
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means no TPU compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def tpu_branch(topo):
    """Trace the kernels' TPU branch, with the persistent compilation cache
    off (a TPU executable cached here could not be read back without a
    chip). Traces are dropped on both sides so that no CPU trace of a fused
    pass is reused here and no TPU trace leaks into later CPU tests."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    from repro.kernels import ops

    cache_was = jax.config.jax_enable_compilation_cache
    interpret_was = ops.INTERPRET
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    ops.INTERPRET = False
    jax.clear_caches()
    yield
    ops.INTERPRET = interpret_was
    jax.clear_caches()
    jax.config.update("jax_enable_compilation_cache", cache_was)
    cc.reset_cache()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    return compiled.as_text()


def _arena(dtype, cap, sh):
    table = _sds((cap, D), DTYPES[dtype], sh)
    xn2 = _sds((cap,), jnp.float32, sh)
    scale = _sds((cap,), jnp.float32, sh) if dtype == "int8" else None
    return table, xn2, scale


@pytest.mark.parametrize("dtype", list(DTYPES))
@pytest.mark.parametrize("rows", [1536, 65536])
def test_fused_gather_pass_compiles(tpu_branch, one_chip, dtype, rows):
    """A gather pass at a rung above MIN_DEVICE_CANDIDATES, from an arena
    of 2^22 rows, at the engine's widest query chunk."""
    from repro.core.verify_engine import _CHUNK_M, _fused_screen

    table, xn2, scale = _arena(dtype, CAP, one_chip)
    hlo = _compile(
        lambda t, n2, sc, r, q: _fused_screen(t, n2, sc, r, q, S),
        table, xn2, scale, _sds((rows,), jnp.int32, one_chip),
        _sds((_CHUNK_M, D), jnp.float32, one_chip))
    assert "tpu_custom_call" in hlo  # the Pallas kernel, not the XLA twin
    assert f"s32[{_CHUNK_M},{2 * S}]" in hlo  # the packed slate comes out


@pytest.mark.parametrize("dtype", list(DTYPES))
def test_fused_full_coverage_pass_compiles(tpu_branch, one_chip, dtype):
    from repro.core.verify_engine import _CHUNK_M, _fused_screen_full

    table, xn2, scale = _arena(dtype, CAP, one_chip)
    hlo = _compile(
        lambda t, n2, sc, msk, q: _fused_screen_full(t, n2, sc, msk, q, S),
        table, xn2, scale, _sds((CAP,), jnp.bool_, one_chip),
        _sds((_CHUNK_M, D), jnp.float32, one_chip))
    assert "tpu_custom_call" in hlo
    assert f"s32[{_CHUNK_M},{2 * S}]" in hlo


@pytest.mark.parametrize("dtype", list(DTYPES))
@pytest.mark.parametrize("m,d,k", [(128, 128, 1), (256, 256, 10),
                                   (8, 256, 50)])
def test_screen_kernels_compile_beyond_chunk(tpu_branch, one_chip, dtype, m,
                                             d, k):
    """The kernels alone, at query batches past the engine's chunk and at
    both served series lengths, so they do not rely on the chunk size."""
    from repro.kernels import ops

    n = 4096
    q = _sds((m, d), jnp.float32, one_chip)
    x = _sds((n, d), DTYPES[dtype], one_chip)
    vec = _sds((n,), jnp.float32, one_chip)
    if dtype == "int8":
        hlo = _compile(
            lambda q, x, sc, n2: ops.screen_select_quant(q, x, sc, n2, k + 8),
            q, x, vec, vec)
    else:
        hlo = _compile(lambda q, x, n2: ops.screen_select(q, x, n2, k + 8),
                       q, x, vec)
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("n,w", [(256, 16), (128, 16)])
def test_paa_kernel_compiles(tpu_branch, one_chip, n, w):
    from repro.core import SummarizationConfig
    from repro.kernels import ops

    cfg = SummarizationConfig(series_len=n, n_segments=w, card_bits=8)
    hlo = _compile(lambda x: ops.paa(x, cfg),
                   _sds((1000, n), jnp.float32, one_chip))
    assert "tpu_custom_call" in hlo


def test_mesh_topk_compiles_on_2x2(tpu_branch, topo):
    """The ``shard="mesh"`` screen over the four chips: queries on one mesh
    axis, candidates on the other, folded by one all-gather."""
    from repro.core.distributed import _mesh_topk_fn

    mesh = Mesh(np.asarray(topo.devices).reshape(2, 2), ("q", "r"),
                axis_types=(AxisType.Auto,) * 2)
    q = _sds((64, D), jnp.float32, NamedSharding(mesh, P("q")))
    x = _sds((2, 1 << 20, D), jnp.float32, NamedSharding(mesh, P("r")))
    hlo = _compile(_mesh_topk_fn(mesh, S), q, x)
    assert "all-gather" in hlo
