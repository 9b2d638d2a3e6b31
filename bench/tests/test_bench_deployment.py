"""A deployment comes from its configuration's files, by name: its fields
(``run.deployment``), its data and query generators (``bench/generators``),
and the window's spans and counters that its readers read. A deployment
added as new files alone runs end to end."""
import hashlib
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from bench import run

from .conftest import ROOT, load, tiny

SEED = 2**31 + 5
SPAN_READERS = ("gateway.service_ms", "plan.host_ms_per_query",
                "execute.host_ms_per_query", "verify.host_ms_per_query",
                "verify.wait_ms_per_query", "verify.gather_fill",
                "host.gc_max_ms")


def _fixed_mapping(c: dict, k: int) -> tuple:
    """The objects that the harness built from a configuration before it
    mapped keys to fields by name: a fixed list of keys."""
    from repro.core import GatewayConfig, StreamConfig, SummarizationConfig

    ix, g = c["index"], c["gateway"]
    stream = StreamConfig(
        scheme=ix["scheme"], summarization=SummarizationConfig(
            series_len=int(c["series_len"]), n_segments=ix["n_segments"],
            card_bits=ix["card_bits"]),
        buffer_entries=ix["buffer_entries"],
        growth_factor=ix["growth_factor"], block_size=ix["block_size"],
        ingest=ix["ingest"], storage=ix["storage"],
        screen_dtype=c["arena_dtype"])
    gateway = GatewayConfig(
        deadline_ms=g["deadline_ms"], slo_p99_ms=g["slo_p99_ms"],
        max_batch=g["max_batch"], k=k, autotune=g["autotune"])
    return stream, gateway


@pytest.mark.parametrize("cell", ["rw256-4m.exact", "rw256-stream.window"])
def test_the_configurations_build_what_the_fixed_mapping_built(cell):
    loaded = load(cell)
    k = int(loaded["mix"]["k"])
    stream, gateway = run.deployment(loaded["config"], k)
    want_stream, want_gateway = _fixed_mapping(loaded["config"], k)
    assert stream.summarization == want_stream.summarization
    assert stream == want_stream
    assert gateway == want_gateway


@pytest.fixture
def no_data(monkeypatch):
    """The random-walk generator, made to fail if it is asked for data."""
    gen = run.module("generators", "random_walk")

    def refuse(*a, **kw):
        raise AssertionError("data generated")

    monkeypatch.setattr(gen, "batches", refuse)
    monkeypatch.setattr(gen, "queries", refuse)


@pytest.mark.parametrize("where, key", [
    ("index", "partitions"), ("gateway", "mesh"),
    ("index", "screen_dtype"), ("gateway", "k")])
def test_an_unknown_key_stops_the_run_before_any_data(monkeypatch, capsys,
                                                      no_data, where, key):
    import jax

    def with_key(name):
        loaded = tiny(load(name))
        loaded["config"][where][key] = 4
        return loaded

    monkeypatch.setattr(run, "require_accelerator",
                        lambda chips: jax.devices()[:chips])
    monkeypatch.setattr(run, "use_compile_cache", lambda: "off")
    monkeypatch.setattr(run, "load_cell", with_key)
    with pytest.raises(SystemExit) as e:
        run.main(["--workload", "rw256-4m.exact", "--seed", str(SEED),
                  "--seconds", "1", "--trace", "0"])
    assert e.value.code not in (0, None)
    assert f"{where}.{key}" in str(e.value.code)
    assert capsys.readouterr().out == ""


def test_index_fields_reach_the_built_index():
    from repro.core import StreamingIndex

    loaded = tiny(load("rw256-4m.exact"))
    loaded["config"]["index"].update(znorm=True, max_lag_entries=8192)
    cell = run.Cell(loaded["config"], loaded["mix"], SEED)
    idx = StreamingIndex(cell.index_cfg)
    try:
        assert idx.lsm.cfg.summarization.znorm is True
        assert idx.pipeline.max_lag_entries == 8192
    finally:
        idx.close()


# sha256 of the parent harness's bench/data.py output on the CPU, seed
# 2**31 + 5: rw256-4m's first batch, rw256-stream's first live batch, and
# 300 queries
PARENT_DIGESTS = {
    "batch": "411c28e466579015fd89306f4397e73d453db4eebe230231eb8964408c276f64",
    "live": "20e817c395fc62c77554157384455e2708f8a10c234fb490f5674f126d432e3a",
    "queries": "25bea08ca49ff552a9a3a321f6d998a6d8a6a2240deb6d4b97aa2a9d0bad4329",
}


def test_random_walk_gives_the_parents_bytes():
    gen = run.module("generators", "random_walk")
    got = {"batch": gen.batches(SEED, 1, 50000, 256)[0],
           "live": gen.batches(SEED, 1, 5000, 256, first=440)[0],
           "queries": gen.queries(SEED, 300, 256, [])}
    for name, x in got.items():
        assert x.dtype == np.float32
        assert hashlib.sha256(x.tobytes()).hexdigest() == \
            PARENT_DIGESTS[name], name


@pytest.mark.parametrize("key", ["generator", "queries"])
def test_an_unknown_generator_is_refused(key):
    loaded = tiny(load("rw256-4m.exact"))
    loaded["config"][key] = "brownian_bridge"
    with pytest.raises(SystemExit, match="brownian_bridge"):
        run.Cell(loaded["config"], loaded["mix"], SEED)


@pytest.fixture(scope="module")
def traced():
    """One traced window of the exact cell at a small size, with batches
    and candidate sets large enough for the device path: 12,000 series,
    a 300 ms deadline at 60 qps."""
    loaded = tiny(load("rw256-4m.exact"))
    loaded["config"]["batch"] = 2000
    loaded["config"]["gateway"]["deadline_ms"] = 300.0
    cell = run.Cell(loaded["config"], loaded["mix"], SEED)
    cell.build(loaded["mix"]["warmup_s"] + 2.0)
    try:
        return cell.serve(2.0, 60.0, trace=True)
    finally:
        cell.close()


def test_the_window_counts_every_engine_counter(traced):
    from repro.core.verify_engine import get_engine

    want = set(run.counters(get_engine().stats))
    assert set(traced.engine) == want
    assert {"calls", "screened", "candidates", "gathered_rows",
            "d2h_copies"} <= want
    assert "batch_hist" not in traced.engine
    assert traced.engine["calls"] > 0
    assert traced.engine["d2h_copies"] == traced.engine["calls"]


def test_reduce_returns_the_windows_spans(traced):
    red = traced.trace
    assert red["spans"]
    assert all(s["name"].startswith("repro.") for s in red["spans"])
    assert {"repro.gateway.batch", "repro.verify.wait"} <= \
        {s["name"] for s in red["spans"]}
    # the CPU backend records no device plane; the keys are there
    assert set(red["idle_by_span"]) == {"idle_s", "dispatcher", "any_thread"}
    assert len(red["busy_s_per_device"]) == red["n_devices"]


@pytest.mark.parametrize("name", SPAN_READERS)
def test_span_readers_read_a_traced_window(traced, name):
    v = run.reader(name)(traced)
    assert isinstance(v, float) and np.isfinite(v) and v > 0


# ---- room for a new deployment: new files only, under a copy of bench/
ROOM_FILES = {
    "configs/room-sines.json": json.dumps({
        "name": "room-sines", "source": "a test deployment",
        "generator": "sines", "queries": "near_copies",
        "series_len": 128, "batches": 6, "batch": 400,
        "index": {"scheme": "TP", "n_segments": 16, "card_bits": 8,
                  "buffer_entries": 256, "block_size": 64,
                  "ingest": "sync", "storage": "model"},
        "arena_dtype": "f32",
        "gateway": {"deadline_ms": 100.0, "max_batch": 16,
                    "slo_p99_ms": 1e9},
        "limits": {"failed": 0, "id_mismatch": 0, "bad_answers": 0,
                   "dist_rel_err": 1e-6}}),
    "mixes/room.json": json.dumps({
        "target_recall": 1.0, "k": 5, "window_newest": 4, "burst": 1,
        "rate_qps": 20, "warmup_s": 0.5, "check_sample": 8,
        "ingest": {"rate_series_per_s": 800, "batch": 200}}),
    "generators/sines.py": '''
import numpy as np


def batches(seed, n_batches, batch, d, first=0):
    t = np.arange(d) / d
    out = []
    for b in range(first, first + n_batches):
        rng = np.random.default_rng([seed, b])
        f = rng.uniform(1, 8, (batch, 3, 1))
        ph = rng.uniform(0, 2 * np.pi, (batch, 3, 1))
        x = np.sin(2 * np.pi * f * t + ph).sum(1)
        out.append((x + rng.normal(0, 0.1, (batch, d))).astype(np.float32))
    return out
''',
    "generators/near_copies.py": '''
import numpy as np


def queries(seed, n, d, history):
    rng = np.random.default_rng([seed, 1 << 30])
    pool = np.concatenate(history)
    x = pool[rng.integers(0, len(pool), n)]
    return (x + rng.normal(0, 0.05, x.shape)).astype(np.float32)
''',
    "metrics/room.flush_ms.py": '''
from bench.spans import durations


def read(win):
    d = durations(win, ("repro.ingest.flush",))
    return sum(d) * 1e3 if d else None
''',
}

# the harness's look for a chip skipped, as a test rehearsal does
RUN_ON_CPU = """import sys, jax
sys.path[:0] = [sys.argv.pop(1), sys.argv.pop(1)]
from bench import run
run.require_accelerator = lambda chips: jax.devices()[:chips]
run.use_compile_cache = lambda: "off"
sys.exit(run.main(sys.argv[1:]))
"""


def test_a_deployment_of_new_files_alone_runs_end_to_end(tmp_path):
    bench = tmp_path / "bench"
    shutil.copytree(run.BENCH, bench,
                    ignore=shutil.ignore_patterns("__pycache__"))
    for rel, text in ROOM_FILES.items():
        assert not (bench / rel).exists()
        (bench / rel).write_text(text)
    spec = json.loads((run.ROOT / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "room-sines", "source": "a test",
                            "file": "bench/configs/room-sines.json",
                            "reduced": [], "why": "room"})
    spec["workloads"].append({"name": "room-sines.room",
                              "config": "room-sines", "traffic": "room",
                              "chips": 1, "why": "room"})
    spec["per_layer"].append({
        "name": "room.flush_ms", "unit": "ms", "better": "lower",
        "source": "program_span", "layer": "ingest", "moves":
        "latency_p50_ms", "workloads": ["room-sines.room"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "-c", RUN_ON_CPU, str(tmp_path),
         os.path.join(ROOT, "src"), "--workload", "room-sines.room",
         "--seed", str(2**33 + 3), "--seconds", "2", "--trace", "1"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-4000:]
    res = json.loads(p.stdout.strip().splitlines()[-1])
    assert res["correct"] is True, res["checks"]
    assert res["metrics"]["room.flush_ms"]["value"] > 0
    assert "(sines)" in p.stderr
    # every file the copy shares with bench/ is as it was
    for path in run.BENCH.rglob("*"):
        if path.is_file() and "__pycache__" not in path.parts:
            rel = path.relative_to(run.BENCH)
            assert (bench / rel).read_bytes() == path.read_bytes(), rel
