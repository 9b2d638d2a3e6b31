"""Distributed Coconut — the paper's pipeline mapped onto a TPU pod mesh.

The paper's two-pass *external sort* (RAM budget vs disk bandwidth) becomes
a *sample-sort* across the mesh (HBM budget vs ICI bisection):

  1. local summarize + sortable-key (the Pallas ingest kernels);
  2. sample local keys, ``all_gather`` the samples, derive range splitters;
  3. bucket every entry by splitter range and exchange buckets with one
     ``all_to_all`` (fixed capacity + sentinel padding — SPMD-friendly);
  4. local bitonic ``lax.sort`` on the received bucket.

The result is a *globally sorted, contiguously sharded* index: shard i holds
a contiguous key range that precedes shard i+1's — exactly the compact &
contiguous layout the paper builds on disk, with the "pod" axis simply the
outermost segment of the range. Bucketing uses the most-significant key word
only, so equal-word ties stay on one shard and global order is preserved.

Queries follow the paper's prune-then-verify plan: replicate the query
batch, compute MINDIST lower bounds against every local entry (VPU), keep
the top-V candidates per query by bound, verify true distances (MXU matmul
form), and reduce a global top-k with one small ``all_gather``. With fixed
verification budget V this is the SPMD analogue of best-first search; V >=
true rank makes it exact (property-tested at small scale).

All functions are written to be used inside ``jax.shard_map`` over an
arbitrary mesh-axis tuple, so the same code runs on the (16,16) single-pod
and (2,16,16) multi-pod production meshes.
"""
from __future__ import annotations

import dataclasses
import functools
import logging
from typing import Sequence

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P

from .summarization import SummarizationConfig, breakpoints
from ..kernels import ref

_SENTINEL = jnp.uint32(0xFFFFFFFF)
_log = logging.getLogger(__name__)


@dataclasses.dataclass(frozen=True)
class DistBuildConfig:
    summarization: SummarizationConfig
    samples_per_shard: int = 64
    capacity_slack: float = 2.0  # bucket capacity = local_n/n_shards * slack
    materialized: bool = True  # carry raw series through the exchange


def _axis_size(axis_names) -> int:
    if isinstance(axis_names, str):
        return lax.axis_size(axis_names)
    size = 1
    for a in axis_names:
        size *= lax.axis_size(a)
    return size


def _summarize_local(series: jnp.ndarray, cfg: SummarizationConfig):
    """Device-side summarize path (ref semantics == the Pallas kernels;
    the compiled TPU build swaps in kernels.ops.summarize)."""
    p = ref.paa_ref(series, cfg.n_segments)
    bps = jnp.asarray(breakpoints(cfg.card_bits))
    sym = ref.sax_ref(p, bps)
    keys = ref.pack_keys_ref(sym, cfg.card_bits, cfg.key_words)
    return p, sym, keys


def build_local(series, ids, cfg: DistBuildConfig, axis_names):
    """shard_map body: sample-sort build. series (ln, n) local shard.

    Returns dict of local sorted arrays + diagnostics; the concatenation of
    shard outputs (shard order) is globally key-sorted.
    """
    scfg = cfg.summarization
    ln = series.shape[0]
    nsh = _axis_size(axis_names)
    _, sym, keys = _summarize_local(series, scfg)
    w0 = keys[:, 0]

    # --- splitters from gathered samples (pass 1 of the "external sort")
    stride = max(1, ln // cfg.samples_per_shard)
    samp = lax.dynamic_slice_in_dim(w0[::stride], 0, min(cfg.samples_per_shard, ln))
    allsamp = lax.all_gather(samp, axis_names, tiled=True)
    ssorted = jnp.sort(allsamp)
    qidx = (jnp.arange(1, nsh, dtype=jnp.int32) * allsamp.shape[0]) // nsh
    splitters = ssorted[qidx]  # (nsh-1,) uint32

    # --- bucket by most-significant key word (ties stay together)
    bucket = jnp.searchsorted(splitters, w0, side="right").astype(jnp.int32)
    cap = max(1, int(ln / nsh * cfg.capacity_slack))
    order = jnp.argsort(bucket, stable=True)
    sbucket = bucket[order]
    start = jnp.searchsorted(sbucket, jnp.arange(nsh, dtype=jnp.int32), side="left")
    pos = jnp.arange(ln, dtype=jnp.int32) - start[sbucket]
    overflow = jnp.sum(pos >= cap)
    slot = jnp.minimum(pos, cap)  # slot `cap` is the shared trash slot

    def scatter(payload, fill):
        buf = jnp.full((nsh, cap + 1) + payload.shape[1:], fill, payload.dtype)
        buf = buf.at[sbucket, slot].set(payload[order])
        return buf[:, :cap]

    send_keys = scatter(keys, _SENTINEL)
    send_ids = scatter(ids.astype(jnp.int32), jnp.int32(-1))
    send_sym = scatter(sym.astype(jnp.int32), jnp.int32(0))
    send_inval = scatter(jnp.zeros((ln,), jnp.int32), jnp.int32(1))
    parts = [send_keys, send_ids, send_sym, send_inval]
    if cfg.materialized:
        parts.append(scatter(series.astype(jnp.float32), jnp.float32(0)))

    # --- one all_to_all bucket exchange (pass 2: the "merge" traffic)
    recv = [
        lax.all_to_all(pt, axis_names, split_axis=0, concat_axis=0, tiled=False)
        for pt in parts
    ]
    rkeys, rids, rsym, rinval = (r.reshape((nsh * cap,) + r.shape[2:]) for r in recv[:4])
    rseries = recv[4].reshape(nsh * cap, -1) if cfg.materialized else None

    # --- local sort; invalid-flag first key pushes sentinels to the end.
    # Sort a permutation (rank-1 operands only), then gather the payloads.
    rn = nsh * cap
    iota = jnp.arange(rn, dtype=jnp.int32)
    operands = (rinval,) + tuple(rkeys[:, i] for i in range(rkeys.shape[1])) + (iota,)
    sorted_all = lax.sort(operands, num_keys=1 + rkeys.shape[1], dimension=0)
    perm = sorted_all[-1]
    nw = rkeys.shape[1]
    out = {
        "invalid": sorted_all[0],
        "keys": jnp.stack(sorted_all[1 : 1 + nw], axis=1),
        "ids": rids[perm],
        "sym": rsym[perm],
        "n_valid": jnp.sum(rinval == 0).astype(jnp.int32)[None],
        "overflow": lax.psum(overflow, axis_names),
    }
    if cfg.materialized:
        out["series"] = rseries[perm]
    return out


def query_local(
    index: dict,
    queries: jnp.ndarray,
    cfg: DistBuildConfig,
    axis_names,
    *,
    k: int = 10,
    verify_budget: int = 128,
):
    """shard_map body: prune-by-LB then verify-top-V then global top-k.

    index: the local shard produced by :func:`build_local` (materialized).
    queries: (m, n) replicated. Returns ((m, k) d2, (m, k) global ids),
    identical on every shard.
    """
    scfg = cfg.summarization
    qp = ref.paa_ref(queries, scfg.n_segments)  # (m, w)
    bps = jnp.asarray(breakpoints(scfg.card_bits))
    big = jnp.float32(1e30)
    lo_e = jnp.concatenate([jnp.array([-big]), bps])
    hi_e = jnp.concatenate([bps, jnp.array([big])])
    sym = index["sym"]
    lo = lo_e[sym]  # (ln, w)
    hi = hi_e[sym]
    inval = index["invalid"].astype(bool)

    below = jnp.maximum(lo[None] - qp[:, None, :], 0.0)
    above = jnp.maximum(qp[:, None, :] - hi[None], 0.0)
    dseg = jnp.maximum(below, above)
    lb2 = scfg.segment_len * jnp.sum(dseg * dseg, axis=-1)  # (m, ln)
    lb2 = jnp.where(inval[None, :], jnp.inf, lb2)

    v = min(verify_budget, sym.shape[0])
    _, cand = lax.top_k(-lb2, v)  # (m, v) local candidate positions
    cseries = index["series"][cand]  # (m, v, n)
    diff = cseries - queries[:, None, :]
    d2 = jnp.sum(diff * diff, axis=-1)  # (m, v)
    d2 = jnp.where(inval[cand], jnp.inf, d2)
    kk = min(k, v)
    nd2, nidx = lax.top_k(-d2, kk)
    local_d2 = -nd2  # (m, kk) ascending? top_k gives descending of -d2 => ascending d2
    local_ids = jnp.take_along_axis(index["ids"][cand], nidx, axis=1)

    # global reduce: gather every shard's top-k and re-select
    gd2 = lax.all_gather(local_d2, axis_names, tiled=False)  # (nsh, m, kk)
    gids = lax.all_gather(local_ids, axis_names, tiled=False)
    nsh = gd2.shape[0]
    gd2 = jnp.moveaxis(gd2, 0, 1).reshape(qp.shape[0], nsh * kk)
    gids = jnp.moveaxis(gids, 0, 1).reshape(qp.shape[0], nsh * kk)
    fd2, fidx = lax.top_k(-gd2, min(k, nsh * kk))
    return -fd2, jnp.take_along_axis(gids, fidx, axis=1)


# --------------------------------------------------------------------------
# jit entry points over a mesh (used by launch/dryrun.py and tests)
# --------------------------------------------------------------------------
def make_build_fn(mesh, axes: Sequence[str], cfg: DistBuildConfig):
    """Returns jit(build) with series/ids sharded over ``axes`` (flattened)."""
    spec_in = P(tuple(axes))
    out_specs = {
        "invalid": spec_in, "keys": spec_in, "ids": spec_in, "sym": spec_in,
        "n_valid": spec_in, "overflow": P(),
    }
    if cfg.materialized:
        out_specs["series"] = spec_in

    @jax.jit
    def build(series, ids):
        f = jax.shard_map(
            functools.partial(build_local, cfg=cfg, axis_names=tuple(axes)),
            mesh=mesh,
            in_specs=(spec_in, spec_in),
            out_specs=out_specs,
        )
        return f(series, ids)

    return build


# --------------------------------------------------------------------------
# mesh-sharded batch serving: queries x runs 2-D screening for the executor
# --------------------------------------------------------------------------
@functools.lru_cache(maxsize=1)
def default_batch_mesh():
    """The default (queries, runs) serving mesh over every local device:
    the query axis gets the largest power-of-two <= sqrt(n_devices) that
    divides the device count, the runs axis the rest."""
    n = jax.device_count()
    qs = 1
    while (qs * 2) * (qs * 2) <= n and n % (qs * 2) == 0:
        qs *= 2
    return jax.make_mesh((qs, n // qs), ("q", "r"),
                         axis_types=(AxisType.Auto,) * 2)


_mesh_topk_cache: dict = {}


def _mesh_topk_fn(mesh, ksel: int):
    """jit'd shard_map: query rows sharded over the first mesh axis, the
    stacked candidate groups over the remaining axes. Each device screens
    its (query shard, candidate shard) tile with one f32 matmul-form
    distance pass and a local top-ksel; the per-shard slates fold with ONE
    ``all_gather`` over the runs axes plus a re-select — the device-side
    analogue of :func:`repro.core.execute.merge_topk_state`."""
    key = (mesh, ksel)
    fn = _mesh_topk_cache.get(key)
    if fn is not None:
        return fn
    axes_r = tuple(mesh.axis_names[1:])

    def body(q, x):
        xl = x.reshape(-1, x.shape[-1])  # (E_local, n)
        # f32 matmul-form screen — the MXU pass, at full f32 precision: the
        # executor certifies the slate against the f32 matmul error bound
        g = jnp.matmul(q, xl.T, precision=lax.Precision.HIGHEST)
        qsq = jnp.sum(q * q, axis=1)
        xsq = jnp.sum(xl * xl, axis=1)
        d2 = qsq[:, None] + xsq[None, :] - 2.0 * g
        kk = min(ksel, xl.shape[0])
        nv, ni = lax.top_k(-d2, kk)  # (mq, kk) of -d2, local rows
        if not axes_r:
            return -nv, ni.astype(jnp.int32)
        ridx = jnp.int32(0)
        for a in axes_r:  # flatten the runs axes into one shard index
            ridx = ridx * lax.axis_size(a) + lax.axis_index(a)
        gi = ni.astype(jnp.int32) + ridx * xl.shape[0]
        av = lax.all_gather(nv, axes_r, tiled=False)  # (nr, mq, kk)
        ai = lax.all_gather(gi, axes_r, tiled=False)
        nr = av.shape[0]
        mq = q.shape[0]
        av = jnp.moveaxis(av, 0, 1).reshape(mq, nr * kk)
        ai = jnp.moveaxis(ai, 0, 1).reshape(mq, nr * kk)
        fv, fi = lax.top_k(av, min(ksel, nr * kk))  # fold the shard slates
        return -fv, jnp.take_along_axis(ai, fi, axis=1)

    q_in, x_in = shardings = _mesh_shardings(mesh)
    f = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(q_in.spec, x_in.spec),
        out_specs=(q_in.spec, q_in.spec),
        # runs-axis outputs are all_gather-reduced (identical on every r
        # shard); the static replication checker cannot infer that.
        check_vma=False,
    )
    fn = jax.jit(f, in_shardings=shardings)
    _mesh_topk_cache[key] = fn
    return fn


def _mesh_shardings(mesh) -> tuple[NamedSharding, NamedSharding]:
    """Where the mesh top-k's inputs live: the padded query batch (mp, n)
    split by rows over the first mesh axis, the stacked candidate groups
    (rs, e, n) over the remaining axes, so each device holds only its own
    tile."""
    axes = mesh.axis_names
    x_spec = P(tuple(axes[1:])) if len(axes) > 1 else P()
    return NamedSharding(mesh, P(axes[0])), NamedSharding(mesh, x_spec)


def _placement(arr) -> str:
    """Which device holds which rows of a sharded array."""
    return f"{arr.shape}: " + ", ".join(
        f"device {s.device.id} holds [{s.index[0].start}:{s.index[0].stop}]"
        for s in arr.addressable_shards)


def mesh_topk_candidates(Q, X, ksel: int, *, mesh=None):
    """Screen a query batch against a candidate table on the device mesh.

    Q (m, n) f32 queries, X (C, n) f32 candidates. The query batch is
    sharded over the mesh's first axis and the candidates over the rest
    (queries x runs 2-D parallelism); each device computes f32 matmul-form
    distances for its tile, and per-shard top-ksel slates fold with one
    ``all_gather``. Returns ((m, ksel) d2 f32, (m, ksel) rows into X,
    -1 = invalid) as host arrays — callers re-rank the slate exactly in
    f64 (see ``execute._rerank_slate``), so the f32 screen never decides
    final distances.

    Candidate rows are padded to a power-of-two-per-shard grid with +large
    sentinels so jit sees a handful of stable shapes across serving
    batches. Each call logs where its inputs landed at DEBUG level."""
    mesh = mesh if mesh is not None else default_batch_mesh()
    axes = mesh.axis_names
    qs = mesh.shape[axes[0]]
    rs = 1
    for a in axes[1:]:
        rs *= mesh.shape[a]
    Q = np.asarray(Q, np.float32)
    X = np.asarray(X, np.float32)
    m, n = Q.shape
    c = X.shape[0]
    if m == 0 or c == 0:
        return np.zeros((m, 0), np.float32), np.full((m, 0), -1, np.int64)
    ksel = min(ksel, c)
    e = -(-c // rs)
    e = max(8, 1 << (e - 1).bit_length())  # pow2 bucket: few jit shapes
    xp = np.full((rs * e, n), 1e15, np.float32)
    xp[:c] = X
    mp = -(-m // qs) * qs
    qp = np.zeros((mp, n), np.float32)
    qp[:m] = Q
    args = jax.device_put((qp, xp.reshape(rs, e, n)), _mesh_shardings(mesh))
    if _log.isEnabledFor(logging.DEBUG):
        _log.debug("mesh top-k queries %s; candidates %s",
                   *(_placement(a) for a in args))
    d2, rows = _mesh_topk_fn(mesh, ksel)(*args)
    d2 = np.asarray(d2)[:m]
    rows = np.asarray(rows).astype(np.int64)[:m]
    return d2, np.where(rows >= c, -1, rows)


def valid_entries(index: dict) -> tuple[np.ndarray, np.ndarray]:
    """Host-side extraction of the valid (non-sentinel) entries of a
    sample-sorted build, in global key order — the bridge from the
    distributed build to the mesh batch executor: the returned (series,
    ids) feed :func:`mesh_topk_candidates` directly, with each build
    shard's contiguous key range landing on one runs-axis shard."""
    inval = np.asarray(index["invalid"]).astype(bool)
    return (
        np.asarray(index["series"])[~inval],
        np.asarray(index["ids"])[~inval].astype(np.int64),
    )


def make_query_fn(mesh, axes: Sequence[str], cfg: DistBuildConfig, *, k=10, verify_budget=128):
    spec_sh = P(tuple(axes))
    in_specs = (
        {"invalid": spec_sh, "keys": spec_sh, "ids": spec_sh, "sym": spec_sh,
         "n_valid": spec_sh, "overflow": P(), "series": spec_sh},
        P(),  # queries replicated
    )

    @jax.jit
    def query(index, queries):
        f = jax.shard_map(
            functools.partial(
                query_local, cfg=cfg, axis_names=tuple(axes), k=k,
                verify_budget=verify_budget,
            ),
            mesh=mesh,
            in_specs=in_specs,
            out_specs=(P(), P()),
            # outputs are all_gather-reduced, i.e. bitwise-identical on every
            # shard; the static replication checker cannot infer that.
            check_vma=False,
        )
        return f(index, queries)

    return query
