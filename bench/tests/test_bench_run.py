"""Each cell's code path rehearsed on the CPU at a tiny size, the result
line's shape, the refusal of a CPU, the low-precision control, and the
faults that ``correct`` has to catch: an answer altered where it is
produced, half of a batch left out, and an ingest that leaves the state
unchanged."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from bench import run

from .conftest import admitted, load

CELLS = ("rw256-4m.exact", "rw256-stream.window", "rw256-4m.approx_window")
ROOT = os.path.join(os.path.dirname(__file__), "..", "..")


@pytest.fixture
def on_cpu(monkeypatch, tiny_cell):
    """``main`` with the look for a chip skipped and the cell shrunk."""
    import jax

    monkeypatch.setattr(run, "require_accelerator",
                        lambda chips: jax.devices()[:chips])
    monkeypatch.setattr(run, "use_compile_cache", lambda: "off")
    monkeypatch.setattr(run, "load_cell", tiny_cell)


def _last_line(capsys) -> dict:
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1])


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_last_line(on_cpu, capsys, cell, trace):
    assert run.main(["--workload", cell, "--seed", str(2**31 + 5),
                     "--seconds", "1.5", "--trace", str(trace)]) == 0
    res = _last_line(capsys)
    keys = list(res)
    assert keys[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert keys[-1] == "checks"
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 0
    assert res["device"]["count"] == 1 and res["device"]["platform"] == "cpu"
    entries = load(cell)["per_layer" if trace else "end_to_end"]
    names = {m["name"] for m in entries}
    assert set(res["metrics"]) <= names
    for name, m in res["metrics"].items():
        assert isinstance(m["value"], (int, float))
    if trace:
        assert {"busy_s", "window_s"} <= set(res["device"])
        assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
    else:
        assert {"setup_s", "latency_p50_ms"} <= set(res["metrics"])
        if cell in admitted():  # every end-to-end metric read
            assert names == set(res["metrics"])
    for c in res["checks"].values():
        assert c["value"] <= c["limit"]


@pytest.mark.parametrize("cell", CELLS)
def test_the_control_in_the_systems_place_is_not_correct(on_cpu, capsys,
                                                         cell):
    """``--control`` puts the reference computed in bfloat16 in place of
    the sampled answers and sends them through the same check."""
    assert run.main(["--workload", cell, "--seed", str(2**31 + 7),
                     "--seconds", "1.5", "--trace", "0", "--control"]) == 0
    res = _last_line(capsys)
    assert res["correct"] is False
    err = res["checks"]["dist_rel_err"]
    assert err["value"] > err["limit"]


def _alter_answer(monkeypatch):
    from repro.core.gateway import Gateway

    orig = Gateway._query_group

    def broken(self, *a, **kw):
        vals, gids = orig(self, *a, **kw)
        gids = gids.copy()
        gids[:, -1] = gids[:, 0]  # the last neighbour replaced
        return vals, gids

    monkeypatch.setattr(Gateway, "_query_group", broken)


def _half_the_batch(monkeypatch):
    """Serve the first half of each formed batch and hand its first answer
    to the rest. Batches of several requests form under a longer deadline."""
    from repro.core.gateway import Gateway

    orig = Gateway._serve_batch

    def broken(self, batch, shed_now):
        keep = batch[:(len(batch) + 1) // 2]
        orig(self, keep, shed_now)
        for req in batch[len(keep):]:
            req.ticket._resolve(keep[0].ticket.result())

    monkeypatch.setattr(Gateway, "_serve_batch", broken)
    load = run.load_cell

    def slow_deadline(name):
        loaded = load(name)
        loaded["config"]["gateway"]["deadline_ms"] = 100.0
        loaded["mix"]["rate_qps"] = 40.0
        return loaded

    monkeypatch.setattr(run, "load_cell", slow_deadline)


def _state_unchanged(monkeypatch):
    """Acknowledge every batch of the live stream without storing it."""
    from repro.core.streaming import StreamingIndex

    orig = StreamingIndex.ingest
    history = run.load_cell("rw256-stream.window")["config"]["batches"]

    def broken(self, series, ts):
        if ts[0] >= history:
            return np.arange(len(series))
        return orig(self, series, ts)

    monkeypatch.setattr(StreamingIndex, "ingest", broken)


@pytest.mark.parametrize("cell,fault", [
    ("rw256-4m.exact", _alter_answer),
    ("rw256-4m.exact", _half_the_batch),
    ("rw256-4m.approx_window", _alter_answer),
    ("rw256-stream.window", _state_unchanged),
])
def test_a_broken_timed_path_is_not_correct(on_cpu, capsys, monkeypatch,
                                            cell, fault):
    fault(monkeypatch)
    assert run.main(["--workload", cell, "--seed", str(2**31 + 9),
                     "--seconds", "1.5", "--trace", "0"]) == 0
    assert _last_line(capsys)["correct"] is False


def test_command_refuses_a_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "-m", "bench.run", "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout == ""
    assert "accelerator" in p.stderr
