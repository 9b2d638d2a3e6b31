"""Pallas kernel: PAA segment means, (B, n) -> (B, w).

The summarization front of the Coconut ingest path. One grid step loads a
(block_b, n) tile of raw series into VMEM, reduces the w segments with one
(n, w) averaging contraction (MXU), and writes the (block_b, w) summary
tile. A 3-D reshape of the tile into segments has no TPU vector layout.

Tiling: n is the series length (<= 1024 in practice); block_b is chosen so
the tile fits comfortably in VMEM (block_b * n * 4B <= ~2 MiB), with the
lane dimension n a multiple of 128 for clean vector layout.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _paa_body(x_ref, o_ref, *, n_segments: int):
    x = x_ref[...].astype(jnp.float32)  # (bb, n)
    n = x.shape[1]
    seg = n // n_segments
    # avg[i, s] = 1/seg where sample i lies in segment s
    row = jax.lax.broadcasted_iota(jnp.int32, (n, n_segments), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (n, n_segments), 1)
    avg = jnp.where(row // seg == col, 1.0 / seg, 0.0).astype(jnp.float32)
    o_ref[...] = jax.lax.dot_general(
        x, avg, (((1,), (0,)), ((), ())), precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )


@functools.partial(jax.jit, static_argnames=("n_segments", "block_b", "interpret"))
def paa_pallas(
    x: jnp.ndarray,
    n_segments: int,
    *,
    block_b: int = 256,
    interpret: bool = False,
) -> jnp.ndarray:
    """x: (B, n) with B % block_b == 0 and n % n_segments == 0 -> (B, w) f32."""
    b, n = x.shape
    assert b % block_b == 0, (b, block_b)
    grid = (b // block_b,)
    return pl.pallas_call(
        functools.partial(_paa_body, n_segments=n_segments),
        grid=grid,
        in_specs=[pl.BlockSpec((block_b, n), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((block_b, n_segments), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, n_segments), jnp.float32),
        interpret=interpret,
    )(x)
