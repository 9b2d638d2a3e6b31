"""Program spans in a synthesized XSpace (``bench/spans.py``): self times,
the device's idle time put down to the dispatcher's innermost span, and
the per-layer readers that read them; each reader reads nothing, and does
not raise, where the window carries no spans or counters."""
import pytest

from bench import run, spans
from bench.trace import reduce

# one formed batch on the dispatcher's line (times in ms): plan, then two
# rounds of launch / wait on the device (with the runtime's own download
# event inside the first wait) / re-rank / certify; ingest on its own
# thread and a collection on the client's. Device operations at [0, 0.2),
# [3.2, 4), [8, 9), so 8 ms of the 10 ms trace are idle.
XSPACE = """
planes {
  id: 1
  name: "/device:TPU:0"
  lines {
    id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 200000000 }
    events { metadata_id: 1 offset_ps: 3200000000 duration_ps: 800000000 }
    events { metadata_id: 1 offset_ps: 8000000000 duration_ps: 1000000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "%fusion.1 = f32[4096,256]{1,0} fusion()" } }
}
planes {
  id: 2
  name: "/host:CPU"
  lines {
    id: 1 name: "gateway-dispatch" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 500000000 duration_ps: 9000000000 stats { metadata_id: 1 int64_value: 1 } stats { metadata_id: 2 int64_value: 16 } stats { metadata_id: 3 int64_value: 16 }}
    events { metadata_id: 2 offset_ps: 500000000 duration_ps: 1000000000 stats { metadata_id: 4 int64_value: 3 }}
    events { metadata_id: 3 offset_ps: 1500000000 duration_ps: 7500000000 stats { metadata_id: 5 int64_value: 16 }}
    events { metadata_id: 4 offset_ps: 1500000000 duration_ps: 3500000000 stats { metadata_id: 6 int64_value: 4 } stats { metadata_id: 7 int64_value: 2048 }}
    events { metadata_id: 5 offset_ps: 1500000000 duration_ps: 500000000 }
    events { metadata_id: 6 offset_ps: 2000000000 duration_ps: 1000000000 stats { metadata_id: 7 int64_value: 2048 } stats { metadata_id: 8 int64_value: 3072 }}
    events { metadata_id: 7 offset_ps: 3000000000 duration_ps: 1500000000 }
    events { metadata_id: 8 offset_ps: 3000000000 duration_ps: 1500000000 }
    events { metadata_id: 9 offset_ps: 4500000000 duration_ps: 300000000 }
    events { metadata_id: 10 offset_ps: 4800000000 duration_ps: 100000000 }
    events { metadata_id: 4 offset_ps: 5000000000 duration_ps: 4000000000 stats { metadata_id: 6 int64_value: 8 } stats { metadata_id: 7 int64_value: 4096 }}
    events { metadata_id: 6 offset_ps: 5000000000 duration_ps: 2500000000 stats { metadata_id: 7 int64_value: 4096 } stats { metadata_id: 8 int64_value: 4096 }}
    events { metadata_id: 8 offset_ps: 7500000000 duration_ps: 1500000000 }
    events { metadata_id: 11 offset_ps: 9500000000 duration_ps: 500000000 }
  }
  lines {
    id: 2 name: "coconut-ingest" timestamp_ns: 0
    events { metadata_id: 12 offset_ps: 6000000000 duration_ps: 1000000000 stats { metadata_id: 7 int64_value: 4096 }}
  }
  lines {
    id: 3 name: "python3" timestamp_ns: 0
    events { metadata_id: 13 offset_ps: 9200000000 duration_ps: 200000000 stats { metadata_id: 9 int64_value: 2 }}
    events { metadata_id: 14 offset_ps: 9600000000 duration_ps: 100000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "repro.gateway.batch" } }
  event_metadata { key: 2 value { id: 2 name: "repro.plan" } }
  event_metadata { key: 3 value { id: 3 name: "repro.execute.blocks" } }
  event_metadata { key: 4 value { id: 4 name: "repro.execute.round" } }
  event_metadata { key: 5 value { id: 5 name: "repro.execute.account" } }
  event_metadata { key: 6 value { id: 6 name: "repro.verify.launch" } }
  event_metadata { key: 7 value { id: 7 name: "np.asarray(jax.Array)" } }
  event_metadata { key: 8 value { id: 8 name: "repro.verify.wait" } }
  event_metadata { key: 9 value { id: 9 name: "repro.verify.rerank" } }
  event_metadata { key: 10 value { id: 10 name: "repro.verify.certify" } }
  event_metadata { key: 11 value { id: 11 name: "repro.gateway.wait" } }
  event_metadata { key: 12 value { id: 12 name: "repro.ingest.flush" } }
  event_metadata { key: 13 value { id: 13 name: "repro.host.gc" } }
  event_metadata { key: 14 value { id: 14 name: "bench.submit" } }
  stat_metadata { key: 1 value { id: 1 name: "batch" } }
  stat_metadata { key: 2 value { id: 2 name: "size" } }
  stat_metadata { key: 3 value { id: 3 name: "rung" } }
  stat_metadata { key: 4 value { id: 4 name: "runs" } }
  stat_metadata { key: 5 value { id: 5 name: "m" } }
  stat_metadata { key: 6 value { id: 6 name: "blocks" } }
  stat_metadata { key: 7 value { id: 7 name: "rows" } }
  stat_metadata { key: 8 value { id: 8 name: "gathered" } }
  stat_metadata { key: 9 value { id: 9 name: "gen" } }
}
"""
MS = 1e-3


@pytest.fixture(scope="module")
def data():
    from jax.profiler import ProfileData

    return ProfileData.from_text_proto(XSPACE)


@pytest.fixture(scope="module")
def window(data):
    red = reduce(data, 0.010)
    return run.Window(
        seconds=0.010, trace=red,
        engine={"calls": 2, "candidates": 6144, "gathered_rows": 7168},
        requests=[run.Request(row=i, due=0.0, window=None, latency_ms=1.0,
                              resp=object(), failed=False) for i in range(4)])


def test_program_spans_carry_line_metadata_and_self_time(window):
    got = window.trace["spans"]
    assert len(got) == 15
    assert all(s["name"].startswith("repro.") for s in got)
    by = {(s["name"], s["start"]): s for s in got}
    batch = by[("repro.gateway.batch", 0.5e6)]
    assert batch["line"] == "/host:CPU/gateway-dispatch"
    assert batch["meta"] == {"batch": 1, "size": 16, "rung": 16}
    assert batch["self"] == pytest.approx(0.5e6)  # 9.5 - 0.5 - 1 - 7.5 ms
    assert by[("repro.execute.blocks", 1.5e6)]["self"] == pytest.approx(0.0)
    assert by[("repro.execute.round", 1.5e6)]["self"] == pytest.approx(0.1e6)
    assert by[("repro.verify.wait", 3.0e6)]["self"] == pytest.approx(1.5e6)


def test_idle_time_goes_to_the_innermost_dispatcher_span(window):
    idle = window.trace["idle_by_span"]
    assert idle["idle_s"] == pytest.approx(8 * MS)
    want = {"repro.verify.launch": 3.5, "repro.verify.wait": 1.2,
            "repro.plan": 1.0, "repro.execute.account": 0.5,
            "repro.gateway.batch": 0.5, "repro.gateway.wait": 0.5,
            "none": 0.3, "repro.verify.rerank": 0.3,
            "repro.execute.round": 0.1, "repro.verify.certify": 0.1}
    assert set(idle["dispatcher"]) == set(want)
    for name, ms in want.items():
        assert idle["dispatcher"][name] == pytest.approx(ms * MS), name
    assert sum(idle["dispatcher"].values()) == pytest.approx(8 * MS)
    assert idle["any_thread"] == pytest.approx(
        {"repro.host.gc": 0.2 * MS, "repro.ingest.*": 1.0 * MS})


@pytest.mark.parametrize("name, want", [
    ("gateway.service_ms", 9.0),
    ("plan.host_ms_per_query", 1.0 / 4),
    ("execute.host_ms_per_query", (0.5 + 0.1) / 4),  # account + round self
    ("verify.host_ms_per_query", (1.0 + 2.5 + 0.3 + 0.1) / 4),
    ("verify.wait_ms_per_query", (1.5 + 1.5) / 4),
    ("verify.gather_fill", 6144 / 7168),
    ("host.gc_max_ms", 0.2),
])
def test_span_readers(window, name, want):
    assert run.reader(name)(window) == pytest.approx(want)


@pytest.mark.parametrize("name", [
    "gateway.service_ms", "plan.host_ms_per_query",
    "execute.host_ms_per_query", "verify.host_ms_per_query",
    "verify.wait_ms_per_query", "verify.gather_fill", "host.gc_max_ms"])
def test_span_readers_read_nothing_without_spans(data, name):
    """A reduction without spans or counters, a program that records
    none, or an untraced window: the reader returns None."""
    reqs = [run.Request(row=0, due=0.0, window=None, latency_ms=1.0,
                        resp=object(), failed=False)]
    bare = dict(reduce(data, 0.010), spans=[])
    no_key = {k: v for k, v in bare.items() if k != "spans"}
    for win in (run.Window(seconds=0.010, trace=no_key,
                           engine={"calls": 2}, requests=reqs),
                run.Window(seconds=0.010, trace=bare, engine={"calls": 2},
                           requests=reqs),
                run.Window(seconds=0.010, engine={"calls": 2}, requests=reqs)):
        assert run.reader(name)(win) is None
