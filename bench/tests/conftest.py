import json
import os
import sys

import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..", "..")
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, ROOT)

from bench import run  # noqa: E402

LOAD_CELL = run.load_cell  # the harness's own, before any test patches it


def admitted() -> set:
    """The cells that BENCHMARK.json holds."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return {w["name"] for w in json.load(f)["workloads"]}


def from_files(name: str) -> dict:
    """A cell that BENCHMARK.json does not hold (PERF.md, Open questions),
    loaded from its files by name: ``bench/configs/<config>.json``,
    ``bench/mixes/<traffic>.json``, and every reader in ``bench/metrics``
    as both an end-to-end and a per-layer metric."""
    config, traffic = name.split(".", 1)
    metrics = [{"name": p.stem, "unit": "-"}
               for p in sorted((run.BENCH / "metrics").glob("*.py"))]
    return {
        "cell": {"name": name, "config": config, "traffic": traffic,
                 "chips": 1},
        "config": run.load_json(run.BENCH / "configs" / f"{config}.json"),
        "mix": run.load_json(run.BENCH / "mixes" / f"{traffic}.json"),
        "end_to_end": metrics, "per_layer": metrics}


def load(name: str) -> dict:
    """A cell as the harness loads it, or from its files where
    BENCHMARK.json does not hold it."""
    return LOAD_CELL(name) if name in admitted() else from_files(name)


def tiny(loaded: dict) -> dict:
    """A cell's configuration and mix at a size the CPU runs in seconds:
    the same code path, a few hundred series, a few requests."""
    c, m = loaded["config"], loaded["mix"]
    c.update(batches=6, batch=400)
    c["gateway"] = dict(c["gateway"], max_batch=16)
    m.update(warmup_s=0.5, rate_qps=8.0, check_sample=8)
    if m.get("window_newest"):
        m["window_newest"] = 3
    if m.get("ingest"):
        m["ingest"] = {"rate_series_per_s": 800, "batch": 400}
    return loaded


@pytest.fixture
def tiny_cell():
    return lambda name: tiny(load(name))
