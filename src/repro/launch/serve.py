"""Serving driver — the end-to-end application, matching the paper's kind
(an exploration/query system): serve batched nearest-neighbor requests over
a live Coconut index while the stream keeps ingesting.

    PYTHONPATH=src python -m repro.launch.serve --scheme BTP --batches 40 \
        --batch-size 500 --query-batch 32

Also supports --mode lm for a toy LM decode-serving loop (smoke config) to
exercise the transformer serving path on this host.
"""
from __future__ import annotations

import argparse
import time

import numpy as np

from ..core import (
    StreamConfig, StreamingIndex, SummarizationConfig, recall_at_k,
    render_heatmap,
)
from ..data.synthetic import seismic


def serve_coconut(args):
    """Serve batched kNN traffic over a live stream.

    ``--tier exact`` answers through the batched exact engine
    (``window_knn_batch``); ``--tier approx`` through the batched
    approximate tier (``window_knn_approx_batch``): one vectorized key seek
    plus coalesced sequential block reads per (run, batch). ``--n-blocks``
    is the approximate tier's recall knob — more adjacent blocks read
    sequentially per query raise recall@k toward exact at sequential-I/O
    prices. Approximate recall@k vs the exact oracle is measured on every
    served batch.

    ``--shard mesh`` executes the exact tier on the local device mesh: the
    query batch sharded over one mesh axis and the live runs over the
    other (queries x runs 2-D ``shard_map``), per-shard top-k states
    folded with one all_gather — answers are identical to the
    single-device engine (host f64 re-rank).

    ``--ingest async`` moves flush/merge work onto the background ingest
    pipeline: ingest submissions return immediately, queries serve from
    pinned epoch snapshots while compactions publish concurrently, and the
    per-batch log line reports the freshness lag (entries not yet in a
    published run, runs awaiting merge, snapshot age).

    Verification runs on the device engine by default: at startup the
    compile cache is pre-warmed with one dummy pass per (arena capacity,
    candidate bucket) the configured stream can produce, so steady-state
    serving executes from cached traces with zero retraces; every per-batch
    log line reports the engine's cumulative ``traces``/``hits`` so compile
    churn is visible immediately. ``--no-prewarm`` skips the warm-up (the
    first batches then pay the compiles)."""
    from ..core.verify_engine import get_engine

    tier = "approx" if args.approx else args.tier
    shard = args.shard if args.shard != "none" else None
    scfg = SummarizationConfig(series_len=args.series_len, n_segments=16,
                               card_bits=8)
    idx = StreamingIndex(StreamConfig(
        scheme=args.scheme, summarization=scfg, buffer_entries=4096,
        growth_factor=4, block_size=512, ingest=args.ingest,
        # getattr: programmatic callers (tests) build partial Namespaces
        storage=getattr(args, "storage", "auto"),
        storage_dir=getattr(args, "storage_dir", None),
        screen_dtype=getattr(args, "screen_dtype", None)))
    if idx.storage is not None:
        print(f"[serve] file storage backend at {idx.storage.root} "
              "(WAL + manifest, crash-consistent)", flush=True)
    idx.raw.disk.keep_log = True
    engine = get_engine()
    if args.prewarm:
        # the non-materialized stream verifies against the RawStore arena,
        # whose capacity walks the bucket ladder as ingest grows it — warm
        # every table size the stream will reach (prewarm dedupes them onto
        # the ladder's actual capacity rungs)
        sizes = sorted({args.batch_size * (b + 1) for b in range(args.batches)})
        t0 = time.time()
        n = engine.prewarm(args.series_len, args.query_batch, args.k, sizes,
                           dtype=getattr(args, "screen_dtype", None))
        print(f"[serve] prewarmed {n} verification traces "
              f"({time.time()-t0:.1f}s) for stores up to {sizes[-1]} entries",
              flush=True)
    lat, recalls, lags = [], [], []
    for b in range(args.batches):
        x = seismic(args.batch_size, args.series_len, seed=b)
        idx.ingest(x, np.full(args.batch_size, b, np.int64))
        if (b + 1) % 5 == 0:  # serve a query batch every 5 ingest batches
            qs = seismic(args.query_batch, args.series_len, seed=10_000 + b)
            t0b, t1b = max(0, b - args.window), b
            t0 = time.time()
            if tier == "approx":
                _, got_ids, _ = idx.window_knn_approx_batch(
                    qs, t0b, t1b, k=args.k, n_blocks=args.n_blocks)
            else:
                _, got_ids, _ = idx.window_knn_batch(qs, t0b, t1b, k=args.k,
                                                     shard=shard)
            dt = (time.time() - t0) / args.query_batch
            lat.append(dt)
            es = engine.stats
            lag = idx.ingest_lag()
            lags.append(lag["lag_entries"])
            bhist = ",".join(f"{mb}:{c}" for mb, c in
                             sorted(es["batch_hist"].items()))
            line = (f"[serve] batch {b+1}: {args.query_batch} queries "
                    f"({tier}{'+mesh' if shard == 'mesh' else ''}), "
                    f"{dt*1e3:.2f} ms/query, "
                    f"partitions={idx.n_partitions}, "
                    f"traces={es['traces']}, hits={es['hits']}, "
                    f"batch_hist={bhist or '-'}, "
                    f"epoch={lag['epoch']}, lag={lag['lag_entries']}, "
                    f"pending_merge={lag['runs_pending_merge']}, "
                    f"snap_age={lag['snapshot_age_s']:.2f}s")
            if tier == "approx":
                # score recall without letting the oracle's reads pollute
                # the approx tier's modeled-I/O figures and access heat
                # map: accounting is suspended for THIS thread only, so a
                # background ingest worker's concurrent flush/merge I/O
                # keeps landing in the shared stats untouched (the old
                # save/restore of d.stats mutated state the worker was
                # accounting into — the reason async+approx used to be
                # rejected)
                with idx.raw.disk.unaccounted():
                    _, exact_ids, _ = idx.window_knn_batch(qs, t0b, t1b,
                                                           k=args.k)
                recalls.append(recall_at_k(got_ids, exact_ids))
                line += f", recall@{args.k}={recalls[-1]:.3f}"
            print(line, flush=True)
    if args.ingest == "async":
        t0 = time.time()
        idx.drain(timeout=300)
        idx.close()
        print(f"[serve] drained ingest backlog in {time.time()-t0:.2f}s "
              f"(max observed lag {max(lags or [0])} entries)")
    lat = np.array(lat) * 1e3
    print(f"[serve] latency ms p50={np.percentile(lat,50):.2f} "
          f"p95={np.percentile(lat,95):.2f} max={lat.max():.2f}")
    if recalls:
        print(f"[serve] approx tier n_blocks={args.n_blocks}: "
              f"mean recall@{args.k}={np.mean(recalls):.3f} "
              f"min={np.min(recalls):.3f}")
    print(f"[serve] ingested {args.batches*args.batch_size} series, "
          f"{idx.n_partitions} partitions, "
          f"index={idx.index_bytes()>>20} MiB, modeled io={idx.raw.disk.modeled_seconds():.2f}s")
    m = idx.measured_io()
    if m:
        print(f"[serve] measured io: wrote "
              f"{(m['raw_write_bytes']+m['run_write_bytes']+m['wal_write_bytes'])/1e6:.1f} MB "
              f"(raw {m['raw_write_bytes']/1e6:.1f}, runs {m['run_write_bytes']/1e6:.1f}, "
              f"wal {m['wal_write_bytes']/1e6:.1f}), read {m['raw_read_bytes']/1e6:.1f} MB, "
              f"{m['manifest_commits']} manifest commits, "
              f"{m['prefetch_spans']} readahead spans")
    print("[serve] access heat map:", render_heatmap(idx.raw.disk.heatmap()))


def serve_gateway(args):
    """Serve an *arrival stream* of independent single-query clients through
    the dynamic-batching gateway (``core.gateway``) while background ingest
    keeps publishing epochs.

    A Poisson generator submits ``--requests`` single queries at
    ``--arrival-rate`` QPS with a deterministic tenant mix (plain exact /
    recall-targeted / conflicting recall+latency targets; half of each with
    a recent-window constraint). The gateway coalesces them into
    ladder-rung batches under ``--deadline-ms``, splits mixed batches into
    per-tier sub-batches against one pinned epoch each, and sheds
    sheddable exact traffic to the approximate tier when the rolling p99
    passes ``--slo-p99-ms``. The summary reports client-observed latency
    percentiles, shed rate, the formed-batch histogram, and the engine's
    post-warm-up retrace count (zero when prewarmed)."""
    import threading

    from ..core import Gateway, GatewayConfig
    from ..core.verify_engine import get_engine

    scfg = SummarizationConfig(series_len=args.series_len, n_segments=16,
                               card_bits=8)
    idx = StreamingIndex(StreamConfig(
        scheme=args.scheme, summarization=scfg, buffer_entries=4096,
        growth_factor=4, block_size=512, ingest="async",
        storage=getattr(args, "storage", "auto"),
        storage_dir=getattr(args, "storage_dir", None),
        screen_dtype=getattr(args, "screen_dtype", None)))
    pre = max(1, (2 * args.batches) // 3)
    for b in range(pre):
        x = seismic(args.batch_size, args.series_len, seed=b)
        idx.ingest(x, np.full(args.batch_size, b, np.int64))
    idx.drain(timeout=300)
    gw = Gateway(idx, GatewayConfig(
        deadline_ms=args.deadline_ms, slo_p99_ms=args.slo_p99_ms,
        max_batch=max(8, args.query_batch), k=args.k,
        autotune=getattr(args, "autotune", False)))
    engine = get_engine()
    if args.prewarm:
        sizes = sorted({args.batch_size * (b + 1) for b in range(args.batches)})
        t0 = time.time()
        n = gw.prewarm(sizes, dtype=getattr(args, "screen_dtype", None))
        print(f"[gateway] prewarmed {n} traces ({time.time()-t0:.1f}s) "
              f"for stores up to {sizes[-1]} entries", flush=True)

    stop = threading.Event()

    def background_ingest():
        for b in range(pre, args.batches):
            if stop.is_set():
                return
            x = seismic(args.batch_size, args.series_len, seed=b)
            idx.ingest(x, np.full(args.batch_size, b, np.int64))
            time.sleep(0.01)

    ingester = threading.Thread(target=background_ingest, daemon=True)
    ingester.start()
    rng = np.random.default_rng(12345)
    Q = seismic(args.requests, args.series_len, seed=77_000)
    warmup = min(args.requests // 4, 2 * max(8, args.query_batch))
    tickets, kinds = [], []
    traces_after_warmup = None
    for i in range(args.requests):
        r = rng.random()
        kw = {}
        if r < 0.2:
            kw["target_recall"] = 0.9
        elif r < 0.3:
            kw.update(target_recall=0.9, latency_budget_ms=0.05)
        if rng.random() < 0.5:
            kw["window"] = (max(0, pre - args.window), pre - 1)
        tickets.append(gw.submit(Q[i], **kw))
        kinds.append("exact" if not kw.get("target_recall") else "approx")
        if i + 1 == warmup:
            for t in tickets:  # drain the warm-up phase before measuring
                t.result(timeout=120)
            gw.reset_slo_window()  # compile latencies must not trip the gate
            traces_after_warmup = engine.stats["traces"]
        if gw.tuner is not None and (i + 1) % 64 == 0:
            st = gw.snapshot()
            print(f"[autotune] req {i+1}: decisions={st.tuner_decisions} "
                  f"explores={st.tuner_explores} "
                  f"observations={st.tuner_observations} "
                  f"probes={st.tuner_probes} batches={st.batches} "
                  f"p99={st.p99_ms:.2f} ms", flush=True)
        time.sleep(rng.exponential(1.0 / max(args.arrival_rate, 1e-6)))
    resps = [t.result(timeout=120) for t in tickets]
    stop.set()
    ingester.join(timeout=30)
    idx.drain(timeout=300)
    measured = resps[warmup:]
    lat = np.array([r.latency_ms for r in measured])
    waits = np.array([r.queue_wait_ms for r in measured])
    shed_rate = float(np.mean([r.shed for r in measured]))
    gs = gw.snapshot_stats()
    retraces = engine.stats["traces"] - (traces_after_warmup
                                         if traces_after_warmup is not None
                                         else engine.stats["traces"])
    bhist = ",".join(f"{s}:{c}" for s, c in sorted(gs["batch_hist"].items()))
    print(f"[gateway] {len(measured)} measured requests @ "
          f"{args.arrival_rate:.0f} QPS offered: "
          f"p50={np.percentile(lat, 50):.2f} ms "
          f"p95={np.percentile(lat, 95):.2f} ms "
          f"p99={np.percentile(lat, 99):.2f} ms "
          f"(queue wait p99={np.percentile(waits, 99):.2f} ms)")
    print(f"[gateway] shed_rate={shed_rate:.3f} shedding={gs['shedding']} "
          f"conflicts={gs['conflicts']} batches={gs['batches']} "
          f"deadline_flushes={gs['deadline_flushes']} "
          f"full_flushes={gs['full_flushes']} batch_hist={bhist}")
    print(f"[gateway] post-warm-up retraces={retraces} "
          f"(traces={engine.stats['traces']}, hits={engine.stats['hits']})")
    if gw.tuner is not None:
        snap = gw.tuner.snapshot()
        for label, arms in snap["profiles"].items():
            fitted = " ".join(
                f"{arm}:p99={est['p99_ms']:.2f}ms,rec={est['recall']:.3f}"
                for arm, est in sorted(arms.items())
                if not arm.startswith("_"))
            print(f"[autotune] profile {label} "
                  f"({arms['_decisions']} decisions, "
                  f"epoch {arms['_last_epoch']}): {fitted}", flush=True)
        for entry in gw.tuner.advise_global(idx.ingest_lag(),
                                            n_series=int(idx.raw.n)):
            print(f"[autotune] [{entry.node_id}] {entry.text}", flush=True)
    gw.close()
    idx.close()


def serve_lm(args):
    import jax
    import jax.numpy as jnp

    from ..configs import get_config
    from ..models.transformer import decode_step, init_params, prefill

    cfg = get_config(args.arch, smoke=True)
    params = init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    B, P = args.query_batch, 32
    toks = rng.integers(0, cfg.vocab, (B, P)).astype(np.int32)
    logits, cache = prefill(params, cfg, {"tokens": jnp.asarray(toks)},
                            cache_len=P + args.decode_tokens)
    step = jax.jit(lambda p, c, t: decode_step(p, cfg, c, t))
    tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    t0 = time.time()
    for _ in range(args.decode_tokens):
        logits, cache = step(params, cache, tok)
        tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    jax.block_until_ready(tok)
    dt = time.time() - t0
    print(f"[serve-lm] {args.decode_tokens} tokens x batch {B}: "
          f"{dt/args.decode_tokens*1e3:.1f} ms/step, "
          f"{B*args.decode_tokens/dt:.0f} tok/s")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", default="coconut", choices=["coconut", "lm"])
    ap.add_argument("--scheme", default="BTP", choices=["PP", "TP", "BTP"])
    ap.add_argument("--batches", type=int, default=40)
    ap.add_argument("--batch-size", type=int, default=500)
    ap.add_argument("--series-len", type=int, default=128)
    ap.add_argument("--query-batch", type=int, default=16)
    ap.add_argument("--window", type=int, default=5)
    ap.add_argument("--k", type=int, default=5)
    ap.add_argument("--tier", default="exact", choices=["exact", "approx"],
                    help="serving tier: exact engine or the approximate "
                         "(key-seek + sequential-block-read) tier")
    ap.add_argument("--n-blocks", type=int, default=2,
                    help="approx tier: adjacent blocks read per (query, run) "
                         "— the recall vs I/O knob")
    ap.add_argument("--shard", default="none", choices=["none", "mesh"],
                    help="exact tier execution: single-device or the device "
                         "mesh (queries x runs 2-D shard_map)")
    ap.add_argument("--ingest", default="sync", choices=["sync", "async"],
                    help="sync: flush/merge inline on the serving thread; "
                         "async: background ingest pipeline (queries never "
                         "block on compaction, freshness lag is logged)")
    ap.add_argument("--storage", default="auto",
                    choices=["auto", "model", "file"],
                    help="storage backend: model (DiskModel simulation), "
                         "file (crash-consistent mmap runs + WAL), or auto "
                         "(the REPRO_STORAGE env var, default model)")
    ap.add_argument("--storage-dir", default=None,
                    help="file backend root directory (default: a fresh "
                         "temp dir); reopening the same dir recovers the "
                         "durable index state")
    ap.add_argument("--screen-dtype", default=None,
                    choices=["f32", "bf16", "int8", "auto"],
                    help="device-arena storage dtype for the screen tier: "
                         "bf16 halves / int8 quarters h2d traffic and "
                         "arena footprint; answers stay exact via the "
                         "widened certificate + f64 re-rank (default: the "
                         "REPRO_SCREEN_DTYPE env var, f32)")
    ap.add_argument("--gateway", action="store_true",
                    help="serve a Poisson arrival stream of independent "
                         "single-query clients through the dynamic-batching "
                         "admission gateway (deadline flush + SLO shedding) "
                         "instead of pre-formed query batches")
    ap.add_argument("--arrival-rate", type=float, default=500.0,
                    help="gateway mode: offered load in queries/second "
                         "(Poisson arrivals)")
    ap.add_argument("--deadline-ms", type=float, default=5.0,
                    help="gateway mode: max in-queue wait before a partial "
                         "batch is flushed (padded to the ladder rung)")
    ap.add_argument("--slo-p99-ms", type=float, default=50.0,
                    help="gateway mode: rolling-p99 latency target; past it "
                         "sheddable exact traffic serves on the approx tier "
                         "until p99 recovers (hysteresis)")
    ap.add_argument("--requests", type=int, default=400,
                    help="gateway mode: total client requests to submit")
    ap.add_argument("--autotune", action="store_true",
                    help="gateway mode: per-request tier selection via the "
                         "online autotuner (measured-feedback bandit over "
                         "the tier/n_blocks grid) instead of the static "
                         "recommender rule; adaptation state is logged "
                         "every 64 requests")
    ap.add_argument("--approx", action="store_true",
                    help="deprecated alias for --tier approx")
    ap.add_argument("--no-prewarm", dest="prewarm", action="store_false",
                    help="skip the verification-engine compile-cache "
                         "warm-up (first batches pay the compiles)")
    ap.add_argument("--arch", default="smollm-360m")
    ap.add_argument("--decode-tokens", type=int, default=32)
    args = ap.parse_args()
    # reject impossible flag combinations at parse time, not mid-batch
    if args.shard == "mesh" and (args.approx or args.tier == "approx"):
        ap.error("--shard mesh serves the exact tier only (the approx "
                 "tier's seek/coalesce I/O model is host-side)")
    if args.mode != "coconut":
        serve_lm(args)
    elif args.gateway:
        serve_gateway(args)
    else:
        serve_coconut(args)


if __name__ == "__main__":
    from .jax_cache import use_persistent_cache

    use_persistent_cache()
    main()
