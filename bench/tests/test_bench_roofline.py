"""The screen kernel's operations, bytes and least time from its shapes."""
import pytest

from bench import roofline

V5E = {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}
HLO = ("%screen_select_pallas.1 = (f32[32,18]{1,0:T(8,128)S(1)}, "
       "s32[32,18]{1,0:T(8,128)S(1)}, f32[32,1]{1,0:T(8,128)}) custom-call("
       "f32[32,256]{1,0:T(8,128)S(1)} %copy-done.2, f32[16384,256]{1,0:T(8,"
       "128)S(1)} %broadcast_select_fusion, f32[1,16384]{1,0:T(1,128)S(1)} "
       "%bitcast.1), custom_call_target=\"tpu_custom_call\"")


def test_shapes_from_the_kernel_operation_text():
    assert roofline.screen_shapes(HLO) == (32, 16384, 256, "f32", 18)
    assert roofline.screen_shapes(
        "%fusion.5 = s32[32,18]{1,0} fusion(s32[32,18]{1,0} "
        "%jit_screen_select_pallas_.5)") is None


def test_a_16_query_pass_at_d256_is_bound_by_bytes():
    flops, nbytes = roofline.screen_cost(16, 65536, 256, "f32", 18)
    assert flops == 2 * 16 * 65536 * 256 + 3 * 16 * 65536
    assert nbytes == (65536 * 256 * 4 + 4 * 65536 + 4 * 16 * 256
                      + 8 * 16 * 18 + 4 * 16)
    assert 7 < flops / nbytes < 9  # about 8 operations per byte
    t, bound = roofline.least_seconds(flops, nbytes, V5E)
    assert bound == "bytes"
    assert t == pytest.approx(nbytes / 819e9)


def test_an_unknown_device_kind_is_an_error():
    assert roofline.load_peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        roofline.load_peaks("cpu")
