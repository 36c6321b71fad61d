"""The join of device ops to the program's named scopes (bench/layers.py)."""
import os

import pytest

from bench import layers as L
from bench import trace as TR

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")

HLO = """HloModule m

%fused_computation (p: f32[8], q: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  %q = f32[8]{0} parameter(1)
  %a = f32[8]{0} abs(%p), metadata={op_name="jit(step)/jvp(stage0)/block0/qact/amax/abs"}
  ROOT %m = f32[8]{0} multiply(%a, %q), metadata={op_name="jit(step)/jvp(stage0)/block0/ubn/mul"}
}

%fused_computation.1 (p: (f32[8], /*index=1*/f32[8])) -> f32[8] {
  %p = (f32[8], /*index=1*/f32[8]) parameter(0)
  %g = f32[8]{0} get-tuple-element(%p), index=0
  ROOT %n = f32[8]{0} negate(%g), metadata={op_name="jit(step)/transpose(jvp(stage0))/block0/ubn/neg"}
}

%ring_body (p: (u32[], /*index=1*/f32[8])) -> (u32[], /*index=1*/f32[8]) {
  %p = (u32[], f32[8]{0}) parameter(0)
  ROOT %r = (u32[], f32[8]{0}) collective-permute(%p), metadata={op_name="jit(step)/shard_map/wire/ppermute"}
}

ENTRY %main (x: f32[8], y: f32[8]) -> f32[8] {
  %x = f32[8]{0} parameter(0), metadata={op_name="x"}
  %y = f32[8]{0} parameter(1)
  %multiply_fusion.2 = f32[8]{0} fusion(%x, %y), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(step)/jvp(stage0)/block0/ubn/mul" stack_frame_id=3}
  %fusion.4 = f32[8]{0} fusion(%multiply_fusion.2), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/transpose(jvp(stage0))/block0/ubn/neg"}
  %quantize_fused.3 = s8[8]{0} custom-call(%fusion.4), custom_call_target="tpu_custom_call", frontend_attributes={kernel_metadata={}}, metadata={op_name="jit(step)/momentum_update/cq/jit(quantize_fused)/pallas_call" stack_frame_id=7}, backend_config={"custom_call_config":{"body":"x"}}
  %copy.5 = f32[8]{0} copy(%fusion.4)
  %while.6 = (u32[], f32[8]{0}) while(%tuple.7), condition=%ring_cond, body=%ring_body, metadata={op_name="jit(step)/shard_map/wire/while"}
  ROOT %t = f32[8]{0} add(%copy.5, %x), metadata={}
}
"""


def test_parse_reads_each_instructions_own_metadata():
    names = L.parse(HLO)["names"]
    # a fusion takes its own instruction's op_name, not its computation's
    assert names["multiply_fusion.2"] == "jit(step)/jvp(stage0)/block0/ubn/mul"
    assert names["quantize_fused.3"] == \
        "jit(step)/momentum_update/cq/jit(quantize_fused)/pallas_call"
    # no metadata, or metadata without an op_name: ""
    assert names["copy.5"] == "" and names["t"] == "" and names["y"] == ""
    assert names["x"] == "x"
    # instructions of fused computations are listed too
    assert names["a"].endswith("/amax/abs")


def test_parse_lists_the_layers_inside_each_fusion_and_the_loops():
    module = L.parse(HLO)
    # fused_computation.1's header holds an "=" (in a tuple comment, as XLA
    # prints them): its op still counts for fusion.4
    assert module["inside"] == {"multiply_fusion.2": {"amax", "ubn"},
                                "fusion.4": {"ubn"}}
    assert module["containers"] == {"while.6"}
    assert module["names"]["r"] == "jit(step)/shard_map/wire/ppermute"


@pytest.mark.parametrize("op_name,phase,layer,stage", [
    ("jit(step)/jvp(stage2)/block1/qconv/jvp()/conv_general_dilated",
     "forward", "qconv", "stage2"),
    ("jit(step)/transpose(jvp(stage0))/block0/qconv/q_e2/amax/abs",
     "backward", "amax", "stage0"),
    ("jit(step)/transpose(jvp(stage3))/block2/qact/q_e1/mul",
     "backward", "q_e1", "stage3"),
    ("jit(step)/momentum_update/cq/amax/reduce_max",
     "optimizer", "amax", None),
    ("momentum_update/cq/amax/reduce_max", "optimizer", "amax", None),
    ("jit(step)/shard_map/momentum_update/update/mul",
     "optimizer", "update", None),
    ("jit(step)/shard_map/wire/ppermute", "wire", "wire", None),
    ("jit(step)/jvp(stem)/ubn/div", "forward", "ubn", None),
    ("jit(step)/jvp(head)/dot_general", "forward", "head", None),
    ("jit(step)/jvp(stage1)/block0/add", "forward", "unscoped", "stage1"),
    ("jit(step)/dynamic_update_slice", "forward", "unscoped", None),
    ("", "unscoped", "unscoped", None),
])
def test_phase_layer_and_stage(op_name, phase, layer, stage):
    assert L.phase(op_name) == phase
    assert L.layer(op_name) == layer
    assert L.stage(op_name) == stage


def test_split_adds_up_to_the_device_time():
    module = L.parse(HLO)
    # two chips, two steps; durations in ns
    a = [("multiply_fusion.2", "other", 0.0, 4e6),
         ("fusion.4", "other", 4e6, 2e6),
         ("quantize_fused.3", "kernel:quantize_fused", 6e6, 1e6),
         ("copy.5", "other", 7e6, 1e6),
         ("while.6", "other", 6e6, 2e6)]    # spans its body's ops: skipped
    b = [("multiply_fusion.2", "other", 0.0, 2e6),
         ("fusion.4", "other", 2e6, 2e6),
         ("quantize_fused.3", "kernel:quantize_fused", 4e6, 3e6),
         ("unknown.1", "other", 7e6, 1e6)]
    parts = L.split([a, b], module, steps=2)
    ph, ly = parts["phases"], parts["layers"]
    assert ph["forward"] == pytest.approx(1.5)       # (4 + 2) / 2 chips / 2
    assert ph["backward"] == pytest.approx(1.0)
    assert ph["optimizer"] == pytest.approx(1.0)
    assert ph["wire"] == 0.0
    assert ph["unscoped"] == pytest.approx(0.5)      # the copy, the unknown
    total = sum(o[3] for ops in (a, b) for o in ops
                if o[0] != "while.6") / 1e6 / 2 / 2
    assert sum(ph.values()) == pytest.approx(total)
    assert ly["ubn"] == pytest.approx(2.5)
    assert ly["cq"] == pytest.approx(1.0)
    assert ly["unscoped"] == pytest.approx(0.5)
    assert ly["mixed"] == pytest.approx(1.5)
    # the amax fused into the ubn op is held, not owned
    assert ly["amax"] == 0.0
    assert parts["holding"]["amax"] == pytest.approx(1.5)
    assert parts["holding"]["ubn"] == pytest.approx(2.5)
    assert parts["stages"] == {"stage0": pytest.approx(2.5)}
    m = L.metrics(parts)
    assert m["ubn_ms_per_step"] == pytest.approx(2.5)
    assert m["optimizer_ms_per_step"] == pytest.approx(1.0)
    # nothing found reads as nothing, not as 0
    assert m["wire_ms_per_step"] is None and m["amax_ms_per_step"] is None
    assert m["wire_exposed_share"] is None


def test_exposed_share_over_two_chips():
    def wire(name):
        return name.startswith("ring")

    # chip 0: wire [0, 10) and [20, 30); compute [5, 25) covers 5 + 5 of 20
    a = [("ring.1", "other", 0.0, 10.0), ("ring.2", "other", 20.0, 10.0),
         ("conv.1", "other", 5.0, 20.0)]
    # chip 1: wire [0, 10), overlapping itself; nothing else runs
    b = [("ring.1", "other", 0.0, 10.0), ("ring.3", "other", 5.0, 5.0)]
    assert L.exposed_share([a, b], wire) == pytest.approx((50.0 + 100.0) / 2)
    # a chip that ran no wire op does not count; none anywhere reads None
    assert L.exposed_share([a, [("conv.1", "other", 0.0, 5.0)]], wire) == \
        pytest.approx(50.0)
    assert L.exposed_share([[("conv.1", "other", 0.0, 5.0)]], wire) is None


def test_join_on_the_recorded_tpu_trace_without_scopes():
    """A program that names no layer (the small trace of test_trace.py):
    every op with an op_name is forward, nothing is in a layer."""
    with open(os.path.join(DATA, "small.hlo.txt")) as f:
        hlo = f.read()
    red = TR.reduce(os.path.join(DATA, "small.xplane.pb"), 1,
                    TR.categories(hlo))
    parts = L.split(red["devices"], L.parse(hlo), steps=3)
    busy = sum(o[3] for o in red["devices"][0]) / 1e6 / 3
    assert sum(parts["phases"].values()) == pytest.approx(busy)
    assert parts["phases"]["forward"] > 0.9 * busy
    assert parts["phases"]["backward"] == parts["phases"]["optimizer"] == 0
    assert parts["layers"]["unscoped"] == pytest.approx(busy)


def test_join_on_the_recorded_scoped_tpu_trace():
    """record_scoped_trace.py's program: named scopes, a jax.grad and the
    quantize kernel under `qact`, traced three times on a TPU v5e."""
    with open(os.path.join(DATA, "small_scoped.hlo.txt")) as f:
        hlo = f.read()
    red = TR.reduce(os.path.join(DATA, "small_scoped.xplane.pb"), 1,
                    TR.categories(hlo))
    module = L.parse(hlo)
    names = module["names"]
    parts = L.split(red["devices"], module, steps=3)
    ph, ly, held = parts["phases"], parts["layers"], parts["holding"]
    busy = sum(o[3] for o in red["devices"][0]) / 1e6 / 3
    assert sum(ph.values()) == pytest.approx(busy)
    assert ph["forward"] > 0 and ph["backward"] > 0
    assert ph["unscoped"] < 0.05 * busy
    # the kernel is named by its pallas_call, not by its jitted wrapper,
    # and sits in the scope it was called under
    kernels = {n for n, c, _, _ in red["devices"][0]
               if c == "kernel:quantize_fused"}
    assert kernels and all(TR.group_name(n) == "quantize_fused"
                           for n in kernels)
    assert all(L.layer(names[n]) == "qact" and L.phase(names[n]) == "forward"
               for n in kernels)
    assert ly["qact"] > 0 and ly["qconv"] > 0 and ly["ubn"] > 0
    # XLA fused the weight update into the weight-gradient conv: the update
    # owns no op, but an op holds it
    assert held["momentum_update"] > 0
    assert held["qconv"] >= ly["qconv"] and held["ubn"] >= ly["ubn"]


def test_async_ops_reads_the_async_line():
    """The async copies (and, across chips, the collectives) run on a line
    of their own, beside the XLA Ops line that trace.reduce reads."""
    path = os.path.join(DATA, "small_scoped.xplane.pb")
    (starts,) = L.async_ops(path, 1)
    groups = {TR.group_name(n) for n, _, _, _ in starts}
    assert len(starts) == 18 and groups == {"copy-start", "slice-start"}
    assert all(dur > 0 for _, _, _, dur in starts)
