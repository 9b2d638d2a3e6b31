"""Pallas kernels: blocked squared-Euclidean-distance scans (MXU form).

The paper's "sequential scan of a contiguous leaf range" re-thought for the
TPU: instead of early-abandoned scalar loops (a disk/CPU idiom), distances
are computed in the matmul form  d2 = |q|^2 + |x|^2 - 2 q.x  on (bm x bn)
tiles streaming through VMEM, with a fused running reduction so the full
(m x n) distance matrix is never materialized in HBM.

Three reductions share the tile pipeline:

* :func:`min_ed_pallas` — per-query running min/argmin (k = 1);
* :func:`topk_ed_pallas` — per-query running top-k: a (bm, k) VMEM
  accumulator of (distance, candidate index) pairs, sorted ascending, is
  merged with each candidate tile by k rounds of min-extraction (pure VPU
  min/where work — no generic sort, so the body also lowers on Mosaic).
  Ties break toward the smaller candidate index, which makes the result
  bit-identical to the lexicographic (d2, index) reference in ref.py.
* :func:`screen_select_pallas` — the verification engine's fused
  screen+select: same running top-k, but the candidate |x|^2 term comes in
  as a precomputed input (the engine's device arena caches centered norms,
  so nothing table-sized is recomputed per pass) and the per-query |q|^2
  needed by the error-bound certificate is emitted alongside the slate —
  one launch replaces the host einsum + argpartition + gather round-trip.

Grid: (m/bm, n/bn) with the candidate axis iterating fastest; the output
tile (the per-query accumulator) is revisited across the candidate axis —
the canonical Pallas accumulation pattern. Block shapes keep the
MXU-aligned contraction (d is zero-padded to a multiple of 128 by ops.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_INT_MAX = 2**31 - 1  # plain int: jnp scalars would be captured as consts


def _ed_scan_body(q_ref, x_ref, min_ref, arg_ref, *, block_n: int, n_blocks: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        min_ref[...] = jnp.full_like(min_ref, jnp.inf)
        arg_ref[...] = jnp.zeros_like(arg_ref)

    d2 = _tile_d2(q_ref, x_ref)  # (bm, bn)
    blk_min = jnp.min(d2, axis=1)
    blk_arg = jnp.argmin(d2, axis=1).astype(jnp.int32) + j * block_n
    cur = min_ref[...]
    take = blk_min < cur
    min_ref[...] = jnp.where(take, blk_min, cur)
    arg_ref[...] = jnp.where(take, blk_arg, arg_ref[...])


def _tile_d2(q_ref, x_ref) -> jnp.ndarray:
    """Squared ED of one (bm, d) x (bn, d) tile: MXU contraction + VPU
    rank-1 corrections."""
    q = q_ref[...].astype(jnp.float32)
    x = x_ref[...].astype(jnp.float32)
    return (
        jnp.sum(q * q, axis=-1, keepdims=True)  # (bm, 1)
        + jnp.sum(x * x, axis=-1)[None, :]  # (1, bn)
        - 2.0 * jax.lax.dot_general(
            q, x, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
    )  # (bm, bn)


def _merge_topk_tile(vals_ref, idxs_ref, d2, tile_idx, k: int) -> None:
    """Merge the sorted (bm, k) accumulator with a fresh (bm, bn) distance
    tile: k rounds of min-extraction over the (bm, k + bn) candidate pool.
    Candidate indices are globally unique within a launch, so masking by
    (value, index) removes exactly one real entry per round; empty slots
    (inf, INT_MAX) collapse together harmlessly."""
    bm = d2.shape[0]
    cand_v = jnp.concatenate([vals_ref[...], d2], axis=1)
    cand_i = jnp.concatenate([idxs_ref[...], tile_idx], axis=1)
    slot = jax.lax.broadcasted_iota(jnp.int32, (bm, k), 1)  # (bm, k)

    def extract(t, carry):
        cv, ov, oi = carry
        best_v = jnp.min(cv, axis=1)  # (bm,)
        tie = cv == best_v[:, None]
        best_i = jnp.min(jnp.where(tie, cand_i, _INT_MAX), axis=1)  # (bm,)
        hit = tie & (cand_i == best_i[:, None])
        cv = jnp.where(hit, jnp.inf, cv)
        write = slot == t
        ov = jnp.where(write, best_v[:, None], ov)
        oi = jnp.where(write, best_i[:, None], oi)
        return cv, ov, oi

    _, out_v, out_i = jax.lax.fori_loop(
        0, k, extract, (cand_v, vals_ref[...], idxs_ref[...])
    )
    vals_ref[...] = out_v
    idxs_ref[...] = out_i


def _topk_ed_body(q_ref, x_ref, vals_ref, idxs_ref, *, k: int, block_n: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        vals_ref[...] = jnp.full_like(vals_ref, jnp.inf)
        idxs_ref[...] = jnp.full_like(idxs_ref, _INT_MAX)

    d2 = _tile_d2(q_ref, x_ref)  # (bm, bn)
    tile_idx = (
        jax.lax.broadcasted_iota(jnp.int32, (d2.shape[0], block_n), 1)
        + j * block_n
    )
    _merge_topk_tile(vals_ref, idxs_ref, d2, tile_idx, k)


def _screen_select_body(
    q_ref, x_ref, xn2_ref, vals_ref, idxs_ref, qn2_ref, *, k: int, block_n: int
):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        vals_ref[...] = jnp.full_like(vals_ref, jnp.inf)
        idxs_ref[...] = jnp.full_like(idxs_ref, _INT_MAX)

    q = q_ref[...].astype(jnp.float32)
    x = x_ref[...].astype(jnp.float32)
    qn2 = jnp.sum(q * q, axis=-1, keepdims=True)  # (bm, 1) certificate |q|^2
    qn2_ref[...] = qn2  # idempotent across the candidate axis
    # matmul-form screen with the PRECOMPUTED candidate norms: the arena
    # caches |x|^2 once per table, so the tile pays one MXU contraction and
    # two rank-1 corrections — never a second pass over x
    d2 = qn2 + xn2_ref[...] - 2.0 * _cross(q, x)  # (bm, bn)
    tile_idx = (
        jax.lax.broadcasted_iota(jnp.int32, (d2.shape[0], block_n), 1)
        + j * block_n
    )
    _merge_topk_tile(vals_ref, idxs_ref, d2, tile_idx, k)


def _screen_select_quant_body(
    q_ref, x_ref, s_ref, xn2_ref, vals_ref, idxs_ref, qn2_ref, *, k: int,
    block_n: int
):
    """The int8 screen body: identical to :func:`_screen_select_body`
    except the candidate tile arrives as int8 values with per-row f32
    scales. The tile upcasts in-register and the scale is applied AFTER
    the MXU contraction (``<q, s*v> = s * <q, v>`` — one (bm, bn) VPU
    multiply instead of rescaling the whole (bn, d) tile); ``xn2`` already
    holds the dequantized norms, so no |x|^2 rescale is needed."""
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        vals_ref[...] = jnp.full_like(vals_ref, jnp.inf)
        idxs_ref[...] = jnp.full_like(idxs_ref, _INT_MAX)

    q = q_ref[...].astype(jnp.float32)
    x = x_ref[...].astype(jnp.float32)  # in-register int8 -> f32 upcast
    qn2 = jnp.sum(q * q, axis=-1, keepdims=True)  # (bm, 1) certificate |q|^2
    qn2_ref[...] = qn2  # idempotent across the candidate axis
    g = _cross(q, x) * s_ref[...]  # (bm, bn) dequantized cross term
    d2 = qn2 + xn2_ref[...] - 2.0 * g
    tile_idx = (
        jax.lax.broadcasted_iota(jnp.int32, (d2.shape[0], block_n), 1)
        + j * block_n
    )
    _merge_topk_tile(vals_ref, idxs_ref, d2, tile_idx, k)


def _cross(q, x) -> jnp.ndarray:
    """(bm, d) x (bn, d) -> (bm, bn) f32 inner products at full f32
    precision: the engine's certificate bounds the screen error by the f32
    matmul term, which a single bf16 MXU pass would exceed."""
    return jax.lax.dot_general(
        q, x, (((1,), (1,)), ((), ())), precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )


def _row_spec(block_n: int) -> pl.BlockSpec:
    """Per-candidate vectors (norms, scales) ride as lane-major (1, n)
    rows: the TPU compiler tiles a 1-D operand with XLA's own 1-D tiling,
    which a (block_n,) block matches only when it spans the whole array."""
    return pl.BlockSpec((1, block_n), lambda i, j: (0, j))


@functools.partial(
    jax.jit, static_argnames=("k", "block_m", "block_n", "interpret")
)
def topk_ed_pallas(
    q: jnp.ndarray,
    x: jnp.ndarray,
    k: int,
    *,
    block_m: int = 128,
    block_n: int = 512,
    interpret: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Per-query k smallest squared EDs over candidates, fused into the scan.

    q: (m, d), x: (n, d); m % block_m == 0, n % block_n == 0, 1 <= k <= n.
    Returns (d2 (m, k) f32 ascending, candidate rows (m, k) int32), ties
    broken toward the smaller candidate index. Slots beyond the number of
    candidates come back as (inf, INT32_MAX) — ops.py maps them to (inf, -1).
    """
    m, d = q.shape
    n, d2_ = x.shape
    assert d == d2_ and m % block_m == 0 and n % block_n == 0, (q.shape, x.shape)
    assert 1 <= k <= n, (k, n)
    grid = (m // block_m, n // block_n)
    return pl.pallas_call(
        functools.partial(_topk_ed_body, k=k, block_n=block_n),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, d), lambda i, j: (i, 0)),
            pl.BlockSpec((block_n, d), lambda i, j: (j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_m, k), lambda i, j: (i, 0)),
            pl.BlockSpec((block_m, k), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m, k), jnp.float32),
            jax.ShapeDtypeStruct((m, k), jnp.int32),
        ],
        interpret=interpret,
    )(q, x)


def _slate_specs(block_m: int, k: int) -> list:
    """Output blocks of the fused screens: the (bm, k) slate pair plus the
    |q|^2 column, kept 2-D as (bm, 1) for the same tiling reason as
    :func:`_row_spec`."""
    return [
        pl.BlockSpec((block_m, k), lambda i, j: (i, 0)),
        pl.BlockSpec((block_m, k), lambda i, j: (i, 0)),
        pl.BlockSpec((block_m, 1), lambda i, j: (i, 0)),
    ]


def _slate_shapes(m: int, k: int) -> list:
    return [
        jax.ShapeDtypeStruct((m, k), jnp.float32),
        jax.ShapeDtypeStruct((m, k), jnp.int32),
        jax.ShapeDtypeStruct((m, 1), jnp.float32),
    ]


@functools.partial(
    jax.jit, static_argnames=("k", "block_m", "block_n", "interpret")
)
def screen_select_pallas(
    q: jnp.ndarray,
    x: jnp.ndarray,
    xn2: jnp.ndarray,
    k: int,
    *,
    block_m: int = 128,
    block_n: int = 512,
    interpret: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Fused verification pass: f32 matmul-form screen + in-kernel top-k
    slate selection + the per-query |q|^2 certificate term, in ONE launch.

    q: (m, d), x: (n, d), xn2: (n,) precomputed candidate squared norms
    (the device arena's cache; pad rows carry a huge sentinel norm so they
    never enter a slate). m % block_m == 0, n % block_n == 0, 1 <= k <= n.
    Returns (d2 (m, k) f32 ascending, candidate rows (m, k) int32,
    |q|^2 (m,) f32). Tie/sentinel semantics match :func:`topk_ed_pallas`;
    the error-bound certificate is d2_true >= d2_screen - 2 * (4 n u
    |q| |x|_max), checked by the engine against the slate's worst entry.
    """
    m, d = q.shape
    n, d2_ = x.shape
    assert d == d2_ and m % block_m == 0 and n % block_n == 0, (q.shape, x.shape)
    assert xn2.shape == (n,), (xn2.shape, n)
    assert 1 <= k <= n, (k, n)
    grid = (m // block_m, n // block_n)
    vals, idxs, qn2 = pl.pallas_call(
        functools.partial(_screen_select_body, k=k, block_n=block_n),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, d), lambda i, j: (i, 0)),
            pl.BlockSpec((block_n, d), lambda i, j: (j, 0)),
            _row_spec(block_n),
        ],
        out_specs=_slate_specs(block_m, k),
        out_shape=_slate_shapes(m, k),
        interpret=interpret,
    )(q, x, xn2.reshape(1, n))
    return vals, idxs, qn2[:, 0]


@functools.partial(
    jax.jit, static_argnames=("k", "block_m", "block_n", "interpret")
)
def screen_select_quant_pallas(
    q: jnp.ndarray,
    x: jnp.ndarray,
    scale: jnp.ndarray,
    xn2: jnp.ndarray,
    k: int,
    *,
    block_m: int = 128,
    block_n: int = 512,
    interpret: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """:func:`screen_select_pallas` over an int8-quantized candidate table.

    q: (m, d) f32, x: (n, d) int8, scale: (n,) f32 per-row dequantization
    scales, xn2: (n,) f32 squared norms of the dequantized rows. Tiles
    upcast to f32 in-register; the scale lands on the contraction output,
    so the screen computes exactly ``|q|^2 + |s v|^2 - 2 s <q, v>`` — the
    f32 distance to the dequantized candidate. Shapes, tie semantics, and
    sentinel behavior match :func:`screen_select_pallas`."""
    m, d = q.shape
    n, d2_ = x.shape
    assert d == d2_ and m % block_m == 0 and n % block_n == 0, (q.shape, x.shape)
    assert scale.shape == (n,), (scale.shape, n)
    assert xn2.shape == (n,), (xn2.shape, n)
    assert 1 <= k <= n, (k, n)
    grid = (m // block_m, n // block_n)
    vals, idxs, qn2 = pl.pallas_call(
        functools.partial(_screen_select_quant_body, k=k, block_n=block_n),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, d), lambda i, j: (i, 0)),
            pl.BlockSpec((block_n, d), lambda i, j: (j, 0)),
            _row_spec(block_n),
            _row_spec(block_n),
        ],
        out_specs=_slate_specs(block_m, k),
        out_shape=_slate_shapes(m, k),
        interpret=interpret,
    )(q, x, scale.reshape(1, n), xn2.reshape(1, n))
    return vals, idxs, qn2[:, 0]


@functools.partial(
    jax.jit, static_argnames=("block_m", "block_n", "interpret")
)
def min_ed_pallas(
    q: jnp.ndarray,
    x: jnp.ndarray,
    *,
    block_m: int = 128,
    block_n: int = 512,
    interpret: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """q: (m, d), x: (n, d); m % block_m == 0, n % block_n == 0.

    Returns (min_d2 (m,) f32, argmin (m,) int32)."""
    m, d = q.shape
    n, d2_ = x.shape
    assert d == d2_ and m % block_m == 0 and n % block_n == 0, (q.shape, x.shape)
    grid = (m // block_m, n // block_n)
    return pl.pallas_call(
        functools.partial(_ed_scan_body, block_n=block_n, n_blocks=n // block_n),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, d), lambda i, j: (i, 0)),
            pl.BlockSpec((block_n, d), lambda i, j: (j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_m,), lambda i, j: (i,)),
            pl.BlockSpec((block_m,), lambda i, j: (i,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m,), jnp.float32),
            jax.ShapeDtypeStruct((m,), jnp.int32),
        ],
        interpret=interpret,
    )(q, x)
