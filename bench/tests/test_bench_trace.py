"""The trace reduction on a small synthesized XSpace: busy union, idle
share, kernel launches, the operations with most time and named gaps."""
import pytest

from bench import trace

# one TPU plane: ops at [0, 2) and [1, 4) overlap, then [6, 7) ms; the
# modules line holds one fused screen launch; during the 2 ms gap between
# 4 and 6 ms the host was in the benchmark's re-rank span, and for 1.5 ms
# of it in a runtime download under that span
XSPACE = """
planes {
  id: 1
  name: "/device:TPU:0"
  lines {
    id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000000 }
    events { metadata_id: 2 offset_ps: 1000000000 duration_ps: 3000000000 }
    events { metadata_id: 1 offset_ps: 6000000000 duration_ps: 1000000000 }
  }
  lines {
    id: 2 name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 3 offset_ps: 0 duration_ps: 4000000000 }
    events { metadata_id: 4 offset_ps: 6000000000 duration_ps: 1000000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "%fusion.1 = f32[4096,256]{1,0} fusion(f32[8192,256]{1,0} %table, s32[4096]{0} %rows)" } }
  event_metadata { key: 2 value { id: 2 name: "%screen_select_pallas.2 = (f32[16,18]{1,0}, s32[16,18]{1,0}, f32[16,1]{1,0}) custom-call(f32[16,256]{1,0:T(8,128)} %q, f32[4096,256]{1,0:T(8,128)} %fusion.1, f32[1,4096]{1,0} %n)" } }
  event_metadata { key: 3 value { id: 3 name: "jit__fused_screen(12)" } }
  event_metadata { key: 4 value { id: 4 name: "jit_other(3)" } }
}
planes {
  id: 2
  name: "/host:CPU"
  lines {
    id: 1 name: "python3" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 3500000000 duration_ps: 3000000000 }
    events { metadata_id: 2 offset_ps: 4500000000 duration_ps: 500000000 }
    events { metadata_id: 3 offset_ps: 4500000000 duration_ps: 1500000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "bench.rerank" } }
  event_metadata { key: 2 value { id: 2 name: "bench.submit" } }
  event_metadata { key: 3 value { id: 3 name: "np.asarray(jax.Array)" } }
}
"""


@pytest.fixture(scope="module")
def reduced():
    from jax.profiler import ProfileData

    return trace.reduce(ProfileData.from_text_proto(XSPACE), 0.010,
                        ("_fused_screen",), ("screen_select",))


def test_busy_is_the_union_of_op_intervals(reduced):
    assert reduced["n_devices"] == 1
    assert reduced["busy_s"] == pytest.approx(0.005)  # [0, 4) and [6, 7)
    assert reduced["busy_s_per_device"] == pytest.approx([0.005])
    assert reduced["window_s"] == 0.010


def test_idle_share_reader(reduced):
    from bench import run

    win = run.Window(seconds=0.010, trace=reduced)
    assert run.reader("device.idle_share")(win) == pytest.approx(0.5)


def test_kernel_launches_are_module_events_by_name(reduced):
    assert reduced["launches"]["_fused_screen"] == pytest.approx([0.004])
    ops = reduced["kernel_ops"]["screen_select"]
    assert [name[:23] for name, _ in ops] == ["%screen_select_pallas.2"]
    assert [s for _, s in ops] == pytest.approx([0.003])


def test_roofline_share_from_the_kernel_shapes(reduced):
    from bench import roofline, run

    win = run.Window(seconds=0.010, trace=reduced,
                     device_kind="TPU v5 lite")
    flops, nbytes = roofline.screen_cost(16, 4096, 256, "f32", 18)
    want = 100 * nbytes / 819e9 / 0.003  # bound by bytes, over 3 ms
    assert run.reader("screen_select_roofline")(win) == pytest.approx(want)


def test_device_ops_sum_per_name(reduced):
    ops = dict(reduced["device_ops"])
    assert ops["%fusion f32[4096,256]"] == pytest.approx(0.003)
    assert ops["%screen_select_pallas f32[16,18]"] == pytest.approx(0.003)
    assert reduced["device_ops"][0][1] >= reduced["device_ops"][1][1]


def test_idle_gap_named_by_the_host_event_overlapping_most(reduced):
    """The runtime's event names the gap, not the spans around it."""
    assert len(reduced["idle_gaps"]) == 1
    name, seconds = reduced["idle_gaps"][0]
    assert seconds == pytest.approx(0.002)
    assert name == "np.asarray(jax.Array) (75% of gap)"
