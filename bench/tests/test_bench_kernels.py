"""Bytes each kernel's contract needs, read from the HLO text that names
its calls in a device trace (texts as a v5e trace records them)."""
import pytest

from bench import kernels, trace

LBS = ("%lbs_pallas.6 = (s32[1,131072]{1,0:T(1,128)S(1)}, "
       "s32[1,131072]{1,0:T(1,128)S(1)}) custom-call(s32[1,4096]"
       "{1,0:T(1,128)S(1)} %fusion.14), custom_call_target=\"tpu_custom_call\"")
COMPACT = ("%compact_tiles_pallas.6 = (s32[1,135168]{1,0:T(1,128)S(1)}, "
           "s32[1,67584]{1,0:T(1,128)S(1)}) custom-call(s32[1,135168]"
           "{1,0:T(1,128)S(1)} %add_convert_fusion, s32[1,135168]"
           "{1,0:T(1,128)S(1)} %fusion.25), custom_call_target=\"tpu_custom_call\"")


def test_lbs_bytes_read_the_scan_and_write_owner_and_rank():
    assert kernels.lbs_bytes(LBS) == 4 * 4096 + 2 * 4 * 131072


def test_compact_bytes_read_items_and_flags_write_items_and_count():
    n = 135168
    assert kernels.compact_bytes(COMPACT) == 4 * n + n + 4 * n + 4


@pytest.mark.parametrize("text,name", [(LBS, "lbs_pallas"),
                                       (COMPACT, "compact_tiles_pallas"),
                                       ("%fusion.21 = s32[8] fusion()",
                                        "fusion")])
def test_kernel_of(text, name):
    assert kernels.kernel_of(text) == name


def test_roofline_share_is_bytes_at_peak_over_time():
    calls = [trace.Event(LBS, 0, 1_000_000), trace.Event(LBS, 2e6, 3e6),
             trace.Event("%fusion.1 = s32[8] fusion()", 3e6, 4e6)]
    share = kernels.roofline_share(calls, "lbs_pallas", 819e9)
    need = 2 * (4 * 4096 + 8 * 131072)
    assert share == pytest.approx(100 * need / 819e9 / 2e-3)
    assert kernels.roofline_share(calls, "compact_tiles_pallas", 1) is None


def test_not_a_custom_call_raises():
    with pytest.raises(ValueError):
        kernels.shapes("%fusion.1 = s32[8] fusion()")
