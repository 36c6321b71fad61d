"""The trace reduction: busy union, idle share, op classes, idle gaps."""
import os

import pytest

from bench import trace as TR

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def test_busy_is_the_union_and_gaps_go_to_host_spans():
    ops = [("fusion.1", "conv", 0.0, 100.0),
           ("fusion.2", "other", 50.0, 100.0),      # overlaps fusion.1
           ("quantize_fused.3", "kernel:quantize_fused", 5000.0, 1000.0)]
    spans = [("bench.window", 0.0, 8000.0),
             ("bench.batch", 200.0, 4000.0),
             ("bench.wait", 6000.0, 8000.0)]
    red = TR.summarize([ops], spans)
    assert red["busy_s"] == pytest.approx(1150e-9)
    assert red["window_s"] == pytest.approx(8000e-9)
    gaps = dict((round(v * 1e9), k) for k, v in red["breakdown"]["idle_gaps"])
    assert gaps == {4850: "bench.batch", 2000: "bench.wait"}
    top = dict(red["breakdown"]["device_ops"])
    assert top["fusion"] == pytest.approx(200e-9)
    assert top["quantize_fused"] == pytest.approx(1000e-9)


def test_busy_averages_over_chips():
    a = [("x.1", "other", 0.0, 400.0)]
    b = [("x.1", "other", 0.0, 200.0)]
    red = TR.summarize([a, b], [("bench.window", 0.0, 1000.0)])
    assert red["busy_s"] == pytest.approx(300e-9)
    ctx = dict(red, steps=1)
    assert TR.op_seconds(ctx, lambda n, c: True) == pytest.approx(300e-9)
    assert TR.op_seconds(ctx, lambda n, c: c == "conv") is None


def test_categories_find_convs_inside_fusions_and_kernels():
    hlo = """HloModule m

%fused_computation (p: f32[2,4,4,8], w: f32[3,3,8,8]) -> f32[] {
  %p = f32[2,4,4,8]{3,2,1,0} parameter(0)
  %w = f32[3,3,8,8]{3,2,1,0} parameter(1)
  %c = f32[2,4,4,8]{3,2,1,0} convolution(%p, %w), window={size=3x3}
  ROOT %r = f32[] reduce(%c, %z), to_apply=%region
}

ENTRY %main (x: f32[2,4,4,8]) -> f32[] {
  %x = f32[2,4,4,8]{3,2,1,0} parameter(0)
  %conv_fusion.3 = f32[] fusion(%x, %w), kind=kOutput, calls=%fused_computation
  %quantize_fused.1 = s8[8,128]{1,0} custom-call(%y), \
custom_call_target="tpu_custom_call"
  ROOT %add.2 = f32[] add(%conv_fusion.3, %conv_fusion.3)
}
"""
    cats = TR.categories(hlo)
    assert cats["conv_fusion.3"] == "conv"
    assert cats["quantize_fused.1"] == "kernel:quantize_fused"
    assert cats["add.2"] == "other"


def test_recorded_tpu_trace():
    path = os.path.join(DATA, "small.xplane.pb")
    with open(os.path.join(DATA, "small.hlo.txt")) as f:
        cats = TR.categories(f.read())
    red = TR.reduce(path, 1, cats)
    assert 0 < red["busy_s"] < red["window_s"]
    seen = {c for _, c, _, _ in red["devices"][0]}
    assert "conv" in seen
    kernels = {n for n, c, _, _ in red["devices"][0]
               if c.startswith("kernel:")}
    assert any("quantize_fused" in n for n in kernels)
    assert any("cq_stochastic" in n for n in kernels)
    # three calls of the program; each call's kernels ran once
    q = [n for n, c, _, _ in red["devices"][0] if "quantize_fused" in n]
    assert len(q) == 3
    names = {k for k, _ in red["breakdown"]["idle_gaps"]}
    assert names <= {"bench.window", "bench.batch", "bench.dispatch",
                     "bench.wait", "none"}
    assert "bench.batch" in names      # the host slept between calls
