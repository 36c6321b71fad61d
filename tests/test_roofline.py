"""Roofline: collective parser on canned HLO + term arithmetic."""
import pytest

from repro.launch.roofline import PEAKS, parse_collectives, peaks, terms

CANNED = """
HloModule jit_f, num_partitions=8
%all-reduce = f32[32,32]{1,0} all-reduce(%dot), channel_id=1, replica_groups=[2,4]<=[8], use_global_device_ids=true, to_apply=%add
%wrapped = f32[] fusion(%all-reduce, %c), kind=kLoop, calls=%wc
%ag = bf16[64,128]{1,0} all-gather(%p0), channel_id=2, replica_groups=[4,2]<=[8], dimensions={0}
%rs = f32[16,32]{1,0} reduce-scatter(%p1), channel_id=3, replica_groups=[2,4]<=[8], to_apply=%add
%cp = s8[1024]{0} collective-permute(%p2), channel_id=4, source_target_pairs={{0,1},{1,0}}
%a2a = f32[8,16]{1,0} all-to-all(%p3), channel_id=5, replica_groups={{0,1,2,3}}
ROOT %all-reduce.1 = f32[] all-reduce(%w), channel_id=6, replica_groups=[4,2]<=[2,4]T(1,0), to_apply=%r
"""


def test_parse_collectives_ops_and_bytes():
    c = parse_collectives(CANNED)
    assert c["all-reduce"]["count"] == 2
    assert c["all-reduce"]["bytes"] == 32 * 32 * 4 + 4
    # all-gather: result 64*128*2 bytes bf16, group 2 -> operand = result/2
    assert c["all-gather"]["bytes"] == 64 * 128 * 2 // 2
    # reduce-scatter: result 16*32*4, group 4 -> operand = result*4
    assert c["reduce-scatter"]["bytes"] == 16 * 32 * 4 * 4
    assert c["collective-permute"]["bytes"] == 1024
    assert c["all-to-all"]["bytes"] == 8 * 16 * 4
    assert c["all-to-all"]["count"] == 1


def test_parse_ignores_operand_name_mentions():
    c = parse_collectives("%x = f32[] fusion(%all-reduce, %c), calls=%wc\n")
    assert c == {}


def test_terms_dominance():
    art = {
        "flops_per_device": 197e12,      # exactly 1 s of bf16 compute
        "bytes_per_device": 819e9 / 2,   # 0.5 s of HBM
        "collective_bytes_per_device": 200e9 / 4,  # 0.25 s of ICI
        "devices": 256,
        "model_flops_global": 197e12 * 256 * 0.8,
    }
    t = terms(art)
    assert t["dominant"] == "compute"
    assert abs(t["compute_s"] - 1.0) < 1e-9
    assert abs(t["memory_s"] - 0.5) < 1e-9
    assert abs(t["collective_s"] - 0.25) < 1e-9
    assert abs(t["roofline_fraction"] - 1.0) < 1e-9
    assert abs(t["useful_ratio"] - 0.8) < 1e-9
    assert abs(t["compute_int8_s"] - 197e12 / 393e12) < 1e-9


def test_terms_memory_bound():
    art = {"flops_per_device": 1e9, "bytes_per_device": 819e9,
           "collective_bytes_per_device": 0, "devices": 2,
           "model_flops_global": 2e9}
    t = terms(art)
    assert t["dominant"] == "memory"
    assert t["roofline_fraction"] < 0.01


def test_peaks_table_is_published_v5e_and_unknown_kind_raises():
    assert PEAKS["TPU v5 lite"] == {"bf16": 197e12, "int8": 393e12,
                                    "hbm": 819e9, "ici": 200e9}
    with pytest.raises(ValueError, match="no published peaks"):
        peaks("cpu")
    with pytest.raises(ValueError):
        terms({"flops_per_device": 1.0, "bytes_per_device": 1.0,
               "collective_bytes_per_device": 0.0, "devices": 1}, kind="cpu")
