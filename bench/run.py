"""Run one benchmark cell on the accelerator and print its result line.

    python3 -m bench.run --workload rw256-4m.exact --seed 7 --seconds 30 --trace 0

A cell is ``<config>.<traffic>`` as ``BENCHMARK.json`` names it. The run
builds the configuration's deployment from ``bench/configs/<config>.json``
(see :func:`deployment`) and its data and queries from the seed, by the
generators that the configuration names in ``bench/generators/``; it warms
the cell's own shapes, then offers the traffic of
``bench/mixes/<traffic>.json`` open loop through the system's gateway for
``--seconds``. Once the window has closed it checks the answers against the
plain f64 reference and prints, as the last line of standard output, one
JSON object: ``correct``, ``attempted``, ``failed``, ``metrics``, ``device``
(and ``breakdown`` and ``idle_by_span`` under ``--trace 1``), then the
numbers compared beside their limits. With ``--trace 0`` the metrics are the cell's end-to-end
metrics; with ``--trace 1`` the window is profiled and the metrics are its
per-layer ones. Each metric is read by ``bench/metrics/<name>.py``.

Two further modes are for sizing and checking a cell. ``--rates r1,r2,...``
sweeps offered rates after one set-up and prints one line per rate instead
of a result. ``--control`` runs the cell as usual, then puts the
low-precision control in the system's place before the check: the sampled
answers are replaced by the reference's own, computed in bfloat16. Its
result line then has to read ``"correct": false``.

Without an accelerator, or with fewer chips than the cell asks for, the
run exits with code 2 and prints no result.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import gc
import importlib.util
import json
import math
import os
import re
import resource
import sys
import time
from pathlib import Path
from typing import Optional

import numpy as np

T_START = time.perf_counter()
BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
TRACE_DIR = ROOT / ".bench_trace"
COMPLETION_GRACE_S = 60.0  # how long past the window a due answer may take
PASSES = ("_fused_screen",)  # jit names of the verification passes
KERNELS = ("screen_select",)  # names of the screen kernels' operations


class NoAccelerator(RuntimeError):
    pass


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# ------------------------------------------------------------ the spec
def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(workload: str, spec: Optional[dict] = None) -> dict:
    """The cell's entry, configuration, traffic mix and metric entries, all
    found by name from ``BENCHMARK.json``."""
    spec = spec or load_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; known: "
                         f"{sorted(cells)}")
    cell = cells[workload]
    conf = {c["name"]: c for c in spec["configs"]}[cell["config"]]

    def applies(m):
        return "workloads" not in m or workload in m["workloads"]

    return {
        "cell": cell,
        "config": load_json(ROOT / conf["file"]),
        "mix": load_json(BENCH / "mixes" / f"{cell['traffic']}.json"),
        "end_to_end": [m for m in spec["end_to_end"] if applies(m)],
        "per_layer": [m for m in spec["per_layer"] if applies(m)],
    }


@functools.cache
def module(kind: str, name: str):
    """``bench/<kind>/<name>.py``, loaded by path: a metric's reader under
    ``metrics``, a data or query generator under ``generators``."""
    path = BENCH / kind / f"{name}.py"
    if not path.is_file():
        raise SystemExit(f"bench: no {name!r} in bench/{kind}")
    mod_name = f"bench_{kind}_" + re.sub(r"\W", "_", name)
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(name: str):
    """The ``read`` function of ``bench/metrics/<name>.py``."""
    return module("metrics", name).read


def require_accelerator(chips: int) -> list:
    """The devices to run on; raises NoAccelerator on a CPU or when fewer
    chips are attached than the cell asks for."""
    import jax

    devs = jax.devices()
    if devs[0].platform == "cpu":
        raise NoAccelerator(f"no accelerator found (JAX sees "
                            f"{devs[0].platform})")
    if len(devs) < chips:
        raise NoAccelerator(f"{chips} chips needed, {len(devs)} found")
    return devs[:chips]


def use_compile_cache() -> str:
    """JAX's persistent compilation cache at ``JAX_COMPILATION_CACHE_DIR``
    when set, else at the fixed ``.jax_cache/`` of the checkout."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(ROOT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path


# ------------------------------------------------------------ one window
@dataclasses.dataclass
class Window:
    """What one measured window produced: the input of every reader.
    Times are seconds from the window's start."""
    seconds: float
    setup_s: float = 0.0
    requests: list = dataclasses.field(default_factory=list)
    engine: dict = dataclasses.field(default_factory=dict)  # counter deltas
    feeds: list = dataclasses.field(default_factory=list)  # feeder log rows
    trace: Optional[dict] = None  # trace.reduce() of the window
    recall: Optional[float] = None  # mean recall@k of approximate answers
    device_kind: str = ""  # as JAX reports it; the key of peaks.json
    lateness_s: float = 0.0  # latest submit behind its due time


@dataclasses.dataclass
class Request:
    """One request due in the window, as the client saw it."""
    row: int  # row of the query array
    due: float
    window: Optional[tuple]
    latency_ms: float  # from due to answer; inf if it failed or missed
    resp: object = None  # the gateway's Response, None if it failed
    failed: bool = False


def counters(stats: dict) -> dict:
    """The numeric counters of ``VerifyEngine.stats``: every key but its
    batch histogram and its dtype."""
    return {k: v for k, v in stats.items()
            if isinstance(v, (int, float)) and not isinstance(v, bool)}


# fields that the harness sets itself: the series length from the top
# level, the arena dtype from ``arena_dtype``, ``k`` from the mix
SET_BY_HARNESS = {"series_len", "summarization", "screen_dtype", "k"}


def deployment(config: dict, k: int) -> tuple:
    """The ``StreamConfig`` (with its ``SummarizationConfig``) and the
    ``GatewayConfig`` that a configuration states. Each key of its ``index``
    object sets the field of that name of ``SummarizationConfig`` or
    ``StreamConfig``, each key of its ``gateway`` object that of
    ``GatewayConfig``; ``series_len`` is the series length, ``arena_dtype``
    the ``screen_dtype``, and ``k`` comes from the mix. A key that names no
    such field stops the run, so that no file runs another deployment than
    the one it states."""
    from repro.core import GatewayConfig, StreamConfig, SummarizationConfig

    def split(where: str, *classes) -> list[dict]:
        parts = [{} for _ in classes]
        for key, value in config[where].items():
            hit = [i for i, cls in enumerate(classes)
                   if key in {f.name for f in dataclasses.fields(cls)}]
            if not hit or key in SET_BY_HARNESS:
                raise SystemExit(
                    f"bench: {where}.{key} in the configuration is no field "
                    f"that it sets of "
                    f"{' or '.join(c.__name__ for c in classes)}")
            parts[hit[0]][key] = value
        return parts

    summ, stream = split("index", SummarizationConfig, StreamConfig)
    (gate,) = split("gateway", GatewayConfig)
    return (StreamConfig(summarization=SummarizationConfig(
                series_len=int(config["series_len"]), **summ),
                screen_dtype=config["arena_dtype"], **stream),
            GatewayConfig(k=k, **gate))


class Cell:
    """One deployment of a configuration and its traffic, built from the
    seed through the system's own entry points. The configuration's
    fields and generators are resolved here, before any data is made."""

    def __init__(self, config: dict, mix: dict, seed: int):
        self.config, self.mix, self.seed = config, mix, seed
        self.d = int(config["series_len"])
        self.k = int(mix["k"])
        # an exact request asks for recall 1: served on the exact tier only
        self.exact = float(mix["target_recall"]) >= 1.0
        self.index_cfg, self.gateway_cfg = deployment(config, self.k)
        self.data = module("generators", config["generator"])
        self.make_queries = module("generators", config["queries"]).queries

    # ---- set-up
    def build(self, stream_s: float) -> None:
        """Generate the data, ingest it, upload the arena and prewarm the
        gateway; generate ``stream_s`` seconds of the ingest stream."""
        from repro.core import Gateway, StreamingIndex
        from repro.core.verify_engine import get_engine

        c = self.config
        t0 = time.perf_counter()
        self.batches = self.data.batches(self.seed, c["batches"], c["batch"],
                                         self.d)
        log(f"[data] {c['batches']} batches of {c['batch']} series x "
            f"{self.d} ({c['generator']}) in {time.perf_counter() - t0:.1f}s")
        t0 = time.perf_counter()
        self.idx = StreamingIndex(self.index_cfg)
        for t, x in enumerate(self.batches):
            self.idx.ingest(x, np.full(x.shape[0], t, np.int64))
        if not self.idx.drain(timeout=1800):
            raise RuntimeError("ingest did not drain")
        log(f"[ingest] {self.idx.raw.n} series, {self.idx.n_partitions} "
            f"runs, ingest + drain {time.perf_counter() - t0:.1f}s")
        self.engine = get_engine()
        self.gw = Gateway(self.idx, self.gateway_cfg)
        # the prewarm runs on a zero table of the arena's capacity; before
        # the upload, so that the two never share the device
        t0 = time.perf_counter()
        n = self.gw.prewarm([self.idx.raw.n])
        log(f"[prewarm] {n} verification traces in "
            f"{time.perf_counter() - t0:.1f}s")
        t0 = time.perf_counter()
        view = self.idx.raw.device_view()
        log(f"[arena] {view.n} rows in a capacity of {view.cap}, {view.dtype},"
            f" {view.nbytes} bytes, built in {time.perf_counter() - t0:.1f}s")
        ing = self.mix.get("ingest")
        self.live = []
        if ing:
            period = ing["batch"] / ing["rate_series_per_s"]
            n_live = math.ceil(stream_s / period) + 1
            self.live = self.data.batches(self.seed, n_live, ing["batch"],
                                          self.d, first=len(self.batches))

    def window_of(self, acked_ts: int):
        """The (t0, t1) window of a request sent now, or None."""
        newest = self.mix.get("window_newest")
        if newest is None:
            return None
        return (max(0, acked_ts - newest + 1), acked_ts)

    def submit(self, q, window):
        import jax

        with jax.profiler.TraceAnnotation("bench.submit"):
            return self.gw.submit(q, k=self.k, window=window,
                                  target_recall=self.mix["target_recall"])

    def burst_sizes(self) -> list[int]:
        """One burst per batch rung, so every rung the window can form has
        run once: a single query, then half of each larger rung plus one."""
        from repro.core.gateway import ladder

        return [1] + [r // 2 + 1 for r in ladder(self.gw.cfg.max_batch)[1:]]

    def warm_bursts(self, Q: np.ndarray, acked_ts: int) -> int:
        row = 0
        for n in self.burst_sizes():
            w = self.window_of(acked_ts)
            tickets = [self.submit(Q[row + j], w) for j in range(n)]
            for t in tickets:
                t.result(timeout=600)
            row += n
        return row

    def close(self) -> None:
        self.gw.close()
        self.idx.close()

    # ---- the measured window
    def serve(self, seconds: float, rate: float, trace: bool) -> Window:
        """Warm-up traffic for ``warmup_s``, then the measured window of
        ``seconds`` at ``rate`` requests per second, with the ingest stream
        running through both where the mix has one."""
        import jax

        from bench import trace as tracing
        from bench import traffic

        mix = self.mix
        warm = float(mix["warmup_s"])
        burst = int(mix.get("burst", 1))
        due_w = traffic.schedule(self.seed, 1, rate, warm, burst)
        due_m = traffic.schedule(self.seed, 2, rate, seconds, burst)
        n_burst = sum(self.burst_sizes())
        Q = self.make_queries(self.seed, n_burst + due_w.size + due_m.size,
                              self.d, self.batches)
        ts0 = len(self.batches)
        row0 = self.warm_bursts(Q, ts0 - 1)
        T0 = time.perf_counter() + 0.05
        w0, w1 = T0 + warm, T0 + warm + seconds
        due = np.concatenate([T0 + due_w, w0 + due_m])
        feeder = None
        ing = mix.get("ingest")
        if ing:
            def ingest(x, ts):
                with jax.profiler.TraceAnnotation("bench.ingest"):
                    self.idx.ingest(x, ts)

            period = ing["batch"] / ing["rate_series_per_s"]
            n_feed = math.ceil((w1 - T0) / period)
            feeder = traffic.Feeder(
                self.live[:n_feed], ts0, T0, period, ingest,
                lambda: self.idx.ingest_lag()["lag_entries"])
            feeder.start()
        acked = (lambda: feeder.acked_ts) if feeder else (lambda: ts0 - 1)
        client = traffic.OpenLoop(
            due, lambda i, w: self.submit(Q[row0 + i], w),
            lambda: self.window_of(acked()))
        client.start()
        time.sleep(max(0.0, w0 - time.perf_counter()))
        setup_s = time.perf_counter() - T_START
        in_use = [memory(jax.local_devices(), "bytes_in_use")]
        if trace:
            tracing.start(str(TRACE_DIR))
            t_on = time.perf_counter()
        before = counters(self.engine.stats)
        compiles = []

        def on_compile(event, secs, **kw):
            if "backend_compile" in event:
                compiles.append(event)

        jax.monitoring.register_event_duration_secs_listener(on_compile)
        time.sleep(max(0.0, w1 - time.perf_counter()))
        jax.monitoring.unregister_event_duration_listener(on_compile)
        after = counters(self.engine.stats)
        in_use.append(memory(jax.local_devices(), "bytes_in_use"))
        log(f"[window] {len(compiles)} compilations inside the window; "
            f"device bytes in use at its open and close {in_use}")
        red = None
        if trace:
            t_off = time.perf_counter()
            red = tracing.reduce(tracing.stop_and_load(str(TRACE_DIR)),
                                 t_off - t_on, PASSES, KERNELS)
            log(f"[trace] {len(red['spans'])} program spans; reduced in "
                f"{time.perf_counter() - t_off:.1f}s")
        client.stop()
        if feeder:
            feeder.stop()
            if feeder.error is not None:
                raise RuntimeError("ingest failed") from feeder.error
        win = Window(seconds=seconds, setup_s=setup_s, trace=red, engine={
            k: v - before.get(k, 0) for k, v in after.items()})
        self.Q = Q[row0:]
        self._collect(win, client, w0, w1, due_w.size)
        if feeder:
            win.feeds = [(d - w0, a - w0, b - w0, n, lag)
                         for d, a, b, n, lag in feeder.log]
            # the fed batches are history now, in ingest order
            self.batches = self.batches + self.live[:len(feeder.log)]
            self.live = self.live[len(feeder.log):]
        return win

    def _collect(self, win: Window, client, w0: float, w1: float,
                 n_warm: int) -> None:
        """Wait for every request due in the window, up to a minute past
        its close, and time each from its due time."""
        deadline = w1 + COMPLETION_GRACE_S
        sent = {s.i: s for s in client.sent}
        late = [s.sent - s.due for s in client.sent]
        win.lateness_s = max(late) if late else 0.0
        for i in range(n_warm, client.due.size):
            s = sent.get(i)
            req = Request(row=i, due=float(client.due[i]) - w0,
                          window=None if s is None else s.window,
                          latency_ms=math.inf, failed=True)
            win.requests.append(req)
            if s is None or s.ticket is None:
                continue
            try:
                resp = s.ticket.result(timeout=max(0.0, deadline
                                                   - time.perf_counter()))
            except Exception as e:  # failed or never came
                log(f"[window] request {i} failed: {e!r}")
                continue
            if self.exact and resp.tier_served != "exact":
                continue  # an exact request answered approximately
            req.resp, req.failed = resp, False
            req.latency_ms = (s.sent + resp.latency_ms / 1e3
                              - client.due[i]) * 1e3


# ------------------------------------------------------------ checking
def sample(cell: Cell, n_done: int) -> np.ndarray:
    """Positions, among the answered requests, of the ``check_sample``
    answers drawn from the seed for the id-for-id comparison."""
    return np.sort(np.random.default_rng([cell.seed, 3]).choice(
        n_done, min(n_done, cell.mix["check_sample"]), replace=False))


def put_control(cell: Cell, win: Window) -> None:
    """The low-precision control in the system's place: each sampled
    answer replaced by the reference's own over the same query and window,
    with data and query rounded to bfloat16 (the configuration states
    float32)."""
    from bench import reference as ref

    done = [r for r in win.requests if not r.failed]
    chosen = [done[i] for i in sample(cell, len(done))]
    if not chosen:
        return
    cv, ci = ref.knn(cell.batches + cell.live,
                     cell.Q[[r.row for r in chosen]], cell.k,
                     [r.window for r in chosen], precision="bf16")
    for r, v, i in zip(chosen, cv, ci):
        r.resp = dataclasses.replace(r.resp, vals=v, ids=i)


def check(cell: Cell, win: Window) -> dict:
    """The numbers compared with the reference, each as [value, limit].

    Every answer's distances are checked against the f64 distances of the
    ids it names. An exact answer must equal the reference id for id, on a
    sample of ``check_sample`` answers drawn from the seed; an approximate
    one must hold k distinct stored ids inside its window, and its recall
    is taken against the reference over every answer."""
    from bench import reference as ref

    lim, k = cell.config["limits"], cell.k
    batches = cell.batches + cell.live
    done = [r for r in win.requests if not r.failed]
    checks = {"failed": [len(win.requests) - len(done), lim["failed"]]}
    if not done:
        return checks
    t0 = time.perf_counter()
    Q = cell.Q[[r.row for r in done]]
    wins = [r.window for r in done]
    ids = np.stack([r.resp.ids for r in done])
    vals = np.stack([r.resp.vals for r in done]).astype(np.float64)
    checks["dist_rel_err"] = [
        ref.dist_rel_err(vals, ref.distances(batches, Q, ids)),
        lim["dist_rel_err"]]
    pick = sample(cell, len(done))
    if cell.exact:
        _, ri = ref.knn(batches, Q[pick], k, [wins[i] for i in pick])
        checks["id_mismatch"] = [ref.id_mismatches(ids[pick], ri),
                                 lim["id_mismatch"]]
    else:
        _, ri = ref.knn(batches, Q, k, wins)
        win.recall = ref.recall(ids, ri)
        checks["bad_answers"] = [ref.bad_answers(ids, wins, batches, k),
                                 lim["bad_answers"]]
    log(f"[reference] {len(done)} answers compared in "
        f"{time.perf_counter() - t0:.1f}s")
    return checks


def passes(checks: dict) -> bool:
    return all(v <= limit for v, limit in checks.values())


# ------------------------------------------------------------ one run
def memory(devices: list, key: str):
    """The largest of the devices' ``memory_stats()[key]``, or None."""
    vals = [(d.memory_stats() or {}).get(key) for d in devices]
    return None if None in vals else max(vals)


def run_cell(loaded: dict, seed: int, seconds: float, trace: bool,
             devices: list, control: bool = False) -> dict:
    """Set up, serve one window, check, and return the result object."""
    config, mix = loaded["config"], loaded["mix"]
    cell = Cell(config, mix, seed)
    cell.build(mix["warmup_s"] + seconds)
    try:
        win = cell.serve(seconds, mix["rate_qps"], trace)
    finally:
        peak = memory(devices, "peak_bytes_in_use")
        cell.close()
    log(f"[window] {len(win.requests)} requests due, generator at most "
        f"{win.lateness_s * 1e3:.3f} ms late; engine {win.engine}; host max "
        f"RSS {resource.getrusage(resource.RUSAGE_SELF).ru_maxrss << 10}")
    # free the system's state before the reference runs
    cell.idx = cell.gw = None
    gc.collect()
    if control:
        put_control(cell, win)
    checks = check(cell, win)
    win.device_kind = devices[0].device_kind
    entries = loaded["per_layer"] if trace else loaded["end_to_end"]
    metrics = {}
    for m in entries:
        v = reader(m["name"])(win)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": peak}
    out = {"correct": passes(checks), "attempted": len(win.requests),
           "failed": checks["failed"][0], "metrics": metrics,
           "device": device}
    if trace:
        device["busy_s"] = win.trace["busy_s"]
        device["window_s"] = win.trace["window_s"]
        out["breakdown"] = {"device_ops": win.trace["device_ops"],
                            "idle_gaps": win.trace["idle_gaps"]}
        out["idle_by_span"] = win.trace["idle_by_span"]
    out["checks"] = {k: {"value": v, "limit": lim}
                     for k, (v, lim) in checks.items()}
    return out


def sweep(loaded: dict, seed: int, seconds: float, rates: list,
          devices: list) -> None:
    """After one set-up, one window per offered rate; one line each."""
    cell = Cell(loaded["config"], loaded["mix"], seed)
    cell.build((loaded["mix"]["warmup_s"] + seconds) * len(rates))
    try:
        for rate in rates:
            win = cell.serve(seconds, rate, False)
            lat = np.array([r.latency_ms for r in win.requests])
            done = np.isfinite(lat)
            ends = np.array([r.due + r.latency_ms / 1e3 for r in win.requests
                             if not r.failed])
            backlog = int((ends > seconds).sum()) if ends.size else 0
            print(json.dumps({
                "rate_qps": rate, "due": int(lat.size),
                "answered": int(done.sum()),
                "answered_after_close": backlog,
                "p50_ms": float(np.percentile(lat[done], 50)) if done.any()
                else None,
                "p95_ms": float(np.percentile(lat[done], 95)) if done.any()
                else None,
                "late_ms": win.lateness_s * 1e3,
                "engine": win.engine}), flush=True)
    finally:
        cell.close()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true",
                    help="put the bfloat16 control in the system's "
                    "place before the check")
    ap.add_argument("--rates", help="sweep these offered rates (qps)")
    args = ap.parse_args(argv)
    loaded = load_cell(args.workload)
    try:
        devices = require_accelerator(loaded["cell"]["chips"])
    except NoAccelerator as e:
        log(f"bench: {e}; this benchmark runs only on an accelerator")
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    log(f"[device] {devices[0].device_kind} x {len(devices)}; compile cache "
        f"at {use_compile_cache()}")
    if args.rates:
        sweep(loaded, args.seed, args.seconds,
              [float(r) for r in args.rates.split(",")], devices)
        return 0
    out = run_cell(loaded, args.seed, args.seconds, bool(args.trace),
                   devices, control=args.control)
    for k, c in out["checks"].items():
        log(f"[check] {k} {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
