"""Program spans on the JAX profiler's host trace (``repro.obs``).

A tiny gateway serves one burst of exact queries under the profiler; the
spans of every layer must land on the dispatcher thread, nested inside the
formed batch's ``repro.gateway.batch``, with the metadata the trace
reduction reads. Without ``jax`` loaded, the numpy host path must record
nothing and import nothing of jax.
"""
import gc
import glob
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import jax
from jax.profiler import ProfileData

from repro import obs
from repro.core import (Gateway, GatewayConfig, StreamConfig, StreamingIndex,
                        SummarizationConfig)
from repro.core.verify_engine import MIN_DEVICE_BATCH, get_engine

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
LEN = 64
CFG = SummarizationConfig(series_len=LEN, n_segments=8, card_bits=6)
BURST = 16  # one formed batch above the device batch floor


def _walks(n, seed):
    rng = np.random.default_rng(seed)
    return rng.standard_normal((n, LEN)).astype(np.float32).cumsum(axis=1)


def _host_events(path) -> dict:
    """Host thread line name -> [(name, start, end, stats)] of ``repro.*``
    events, in the order the trace holds them."""
    pb = glob.glob(os.path.join(path, "**", "*.xplane.pb"), recursive=True)
    with open(pb[0], "rb") as f:
        data = ProfileData.from_serialized_xspace(f.read())
    lines = {}
    for plane in data.planes:
        if not plane.name.startswith("/host:CPU"):
            continue
        for line in plane.lines:
            evs = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
                    dict(ev.stats)) for ev in line.events
                   if ev.name.startswith("repro.")]
            if evs:
                lines[line.name] = lines.get(line.name, []) + evs
    return lines


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """One burst of exact queries through a gateway over 20,000 walks,
    traced; returns (the dispatcher line's events, every line's events,
    the engine's counter deltas)."""
    idx = StreamingIndex(StreamConfig(scheme="BTP", summarization=CFG,
                                      buffer_entries=4096, growth_factor=4,
                                      block_size=128))
    for b in range(5):
        idx.ingest(_walks(4000, 10 + b), np.full(4000, b, np.int64))
    gw = Gateway(idx, GatewayConfig(deadline_ms=500.0, max_batch=BURST, k=5))
    eng = get_engine()
    Q = _walks(BURST, 99)
    # warm the shapes first, so the traced batch holds no compile
    for t in [gw.submit(q) for q in Q]:
        t.result(timeout=300)
    keys = ("calls", "candidates", "gathered_rows")
    before = {k: eng.stats[k] for k in keys}
    path = str(tmp_path_factory.mktemp("trace"))
    jax.profiler.start_trace(path)
    try:
        for t in [gw.submit(q) for q in Q]:
            t.result(timeout=300)
        gc.collect()
        gw.close()  # ends the dispatcher's next wait inside the trace
    finally:
        jax.profiler.stop_trace()
        gw.close()
        idx.close()
    lines = _host_events(path)
    dispatch = [evs for evs in lines.values()
                if any(n == "repro.gateway.batch" for n, *_ in evs)]
    assert len(dispatch) == 1, sorted(lines)
    return dispatch[0], lines, {k: eng.stats[k] - before[k] for k in keys}


def test_every_layer_spans_inside_its_formed_batch(traced):
    evs, _, _ = traced
    batches = [e for e in evs if e[0] == "repro.gateway.batch"]
    assert len(batches) == 1
    _, b0, b1, meta = batches[0]
    assert meta["size"] == BURST and meta["rung"] == BURST
    assert meta["batch"] >= 2  # the warm-up burst formed the first
    names = {n for n, *_ in evs}
    assert {"repro.gateway.wait", "repro.plan", "repro.execute.blocks",
            "repro.execute.round", "repro.execute.account",
            "repro.verify.launch", "repro.verify.wait",
            "repro.verify.rerank", "repro.verify.certify"} <= names
    for name, s, e, _ in evs:
        if name.startswith(("repro.plan", "repro.execute.", "repro.verify.")):
            assert b0 <= s <= e <= b1, name
    plan = [m for n, _, _, m in evs if n == "repro.plan"]
    assert plan and all(m["tier"] == "exact" and m["runs"] >= 1 for m in plan)


def test_launch_metadata_and_counters_agree(traced):
    evs, _, delta = traced
    launches = [m for n, _, _, m in evs if n == "repro.verify.launch"]
    assert len(launches) == delta["calls"] > 0
    for m in launches:
        assert m["m"] >= MIN_DEVICE_BATCH and m["bucket"] >= m["m"]
        assert 0 < m["rows"] <= m["gathered"]
    assert sum(m["rows"] for m in launches) == delta["candidates"]
    assert sum(m["gathered"] for m in launches) == delta["gathered_rows"]


def test_each_collection_is_a_span_while_tracing(traced):
    _, lines, _ = traced
    gcs = [m for evs in lines.values() for n, _, _, m in evs
           if n == "repro.host.gc"]
    assert any(m["gen"] == 2 for m in gcs)  # the explicit gc.collect()


def test_span_while_nothing_traces_is_the_shared_noop():
    with obs.span("repro.test", rows=3) as s:
        s.set_metadata(more=1)
    assert obs.span("repro.a") is obs.span("repro.b", rows=1)
    code = textwrap.dedent("""
        import sys
        from repro import obs
        assert obs.span("repro.a") is obs.span("repro.b", rows=1)
        assert "jax" not in sys.modules
    """)
    _run_without_jax(code)


def test_the_host_path_imports_no_jax():
    """Importing the executor and serving a plan on the numpy backend
    loads no jax: the spans stay no-ops there."""
    code = textwrap.dedent("""
        import sys
        import numpy as np
        from repro.core.execute import execute  # noqa: F401
        from repro.core import CTree, CTreeConfig, RawStore, SummarizationConfig
        cfg = SummarizationConfig(series_len=64, n_segments=8, card_bits=6)
        rng = np.random.default_rng(0)
        X = rng.standard_normal((3000, 64)).astype(np.float32).cumsum(axis=1)
        raw = RawStore(64)
        ct = CTree(CTreeConfig(summarization=cfg, block_size=128))
        ct.bulk_build(X, raw.append(X))
        Q = X[:12] + 0.01
        vals, ids, _ = ct.knn_batch(Q, 5, raw=raw, backend="numpy")
        assert (ids[:, 0] == np.arange(12)).all()
        loaded = sorted(m for m in sys.modules if m.split(".")[0] == "jax")
        assert not loaded, loaded
    """)
    _run_without_jax(code)


def _run_without_jax(code: str) -> None:
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    proc = subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
