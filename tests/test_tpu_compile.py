"""Ahead-of-time compiles of the Pallas kernels for a described TPU v5e.

Nothing runs on a chip.  Each test lowers one kernel at real widths for one
chip of a described v5e:2x2 topology and compiles it with the TPU compiler,
which refuses what interpret mode accepts: blocks that break the tiling
rule, VMEM overruns, casts Mosaic lacks.  Every compiled program must hold
the kernel (`tpu_custom_call`).  The topology is described inside a fixture
so that only the test worker that runs this file loads the TPU library.
"""
from __future__ import annotations

import math
import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import ops
from repro.kernels.backward import bwd_dgrad, bwd_wgrad
from repro.kernels.page_gather import page_gather
from repro.kernels.paged_attention import flash_attention, paged_attention
from repro.kernels.qmatmul import qmatmul
from repro.kernels.quantize import cq_stochastic, quantize_fused
from repro.kernels.selective_scan import selective_scan
from repro.kernels.ubn import ubn_norm

KERNEL = 'custom_call_target="tpu_custom_call"'

# granite-3-8b (configs/granite_3_8b.py): d_model 4096, d_ff 12800, 32 heads
# and 8 KV heads of head_dim 128; 4096 tokens per matmul
TOK, D, FF, H, KV, DH = 4096, 4096, 12800, 32, 8, 128

# every (rows, channels) view qbatchnorm hands ubn_norm_op in ResNet-50 at
# batch 32, 224 px (test_resnet50_bn_shapes_are_the_models checks the list)
RESNET50_BN = [(100352, 64), (100352, 128), (100352, 256), (25088, 128),
               (25088, 256), (25088, 512), (6272, 256), (6272, 512),
               (6272, 1024), (1568, 512), (1568, 2048)]
RESNET50_BN_KERNEL = [s for s in RESNET50_BN if s[0] <= 25088]


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one, so keep the cache off around these
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield jax.sharding.SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(sharding, fn, *shapes):
    """Compile fn for the described chip at (shape, dtype) args; returns
    the compiled program's text after checking it holds a kernel."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert KERNEL in text
    return text


F32, I8, I32 = jnp.float32, jnp.int8, jnp.int32
SCALAR = ((), F32)


@pytest.mark.parametrize("requant", [False, True])
def test_qmatmul_compiles(one_chip, requant):
    if requant:
        _compile(one_chip, lambda a, b, s: qmatmul(a, b, s, interpret=False),
                 ((TOK, D), I8), ((D, FF), I8), SCALAR)
    else:
        _compile(one_chip, lambda a, b: qmatmul(a, b, interpret=False),
                 ((TOK, D), I8), ((D, FF), I8))


@pytest.mark.parametrize("mode", ["affine", "flag"])
def test_bwd_dgrad_compiles(one_chip, mode):
    _compile(one_chip,
             lambda g, b, s: bwd_dgrad(g, b, s, mode=mode, k=8,
                                       interpret=False),
             ((TOK, FF), F32), ((D, FF), I8), ((3,), F32))


@pytest.mark.parametrize("mode", ["affine", "flag"])
def test_bwd_wgrad_compiles(one_chip, mode):
    _compile(one_chip,
             lambda a, g, s: bwd_wgrad(a, g, s, mode=mode, k=8,
                                       interpret=False),
             ((TOK, D), I8), ((TOK, FF), F32), ((3,), F32))


# Q_A and Q_W at ResNet-50 batch 64: activations of stages 0-3, a 3x3 and a
# 1x1 HWIO weight, and a width with ragged lanes
RESNET50_QUANTIZE = {
    "s0_act64": (64, 56, 56, 64), "s0_act256": (64, 56, 56, 256),
    "s1_act512": (64, 28, 28, 512), "s3_act2048": (64, 7, 7, 2048),
    "w3x3": (3, 3, 512, 512), "w1x1": (1, 1, 1024, 2048),
    "ragged": (8, 7, 7, 2000)}


@pytest.mark.parametrize(
    "shape", [(TOK, D), (1, 32 * 56 * 56 * 64),
              *RESNET50_QUANTIZE.values()],
    ids=["matrix", "flattened_activation", *RESNET50_QUANTIZE])
def test_quantize_fused_compiles(one_chip, shape):
    _compile(one_chip, lambda x, s: quantize_fused(x, s, interpret=False),
             (shape, F32), SCALAR)


def _hlo_shapes(text):
    """(opcode, dtype, element count) of every instruction in HLO text."""
    out = []
    for m in re.finditer(r"= (\w+)\[([\d,]*)\]\S* ([\w-]+)\(", text):
        dims = [int(d) for d in m.group(2).split(",") if d]
        out.append((m.group(3), m.group(1), math.prod(dims)))
    return out


def test_quantize_meets_activation_through_bitcasts(one_chip, monkeypatch):
    """ReLU -> Q_A (qtensor._decompose) -> dequantize at a ResNet-50 b64
    stage 0 activation: the kernel's operand and result reach the
    activation through bitcasts alone, with no relayout (reshape, copy,
    transpose, while loop or dynamic-update-slice) of its f32 or s8
    buffer on either side; both stay in HBM (no `S(1)`, VMEM)."""
    from repro.core.qtensor import get_quantizer
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    shape = (64, 56, 56, 64)

    def f(x):
        return get_quantizer("grid", 8).quantize(jax.nn.relu(x)).dequantize()

    text = _compile(one_chip, f, (shape, F32))
    kernel = re.search(r"= s8\[[\d,]+\](\S*) custom-call\(%([\w.-]+)",
                       text)
    assert kernel is not None and "S(1)" not in kernel.group(1)
    operand = re.search(rf"%{re.escape(kernel.group(2))} = (\S+) (\w+)\(",
                        text)
    assert operand.group(2) == "bitcast" and "S(1)" not in operand.group(1)
    n = math.prod(shape)
    moves = [(op, dt) for op, dt, size in _hlo_shapes(text)
             if size == n and dt in ("f32", "s8") and op in (
                 "reshape", "copy", "transpose", "while",
                 "dynamic-update-slice")]
    assert moves == []


def test_cq_stochastic_compiles(one_chip):
    _compile(one_chip,
             lambda x, b, s: cq_stochastic(x, b, s, interpret=False),
             ((D, FF), F32), ((D, FF), jnp.uint32), SCALAR)


@pytest.mark.parametrize("m,c", RESNET50_BN_KERNEL,
                         ids=[f"{m}x{c}" for m, c in RESNET50_BN_KERNEL])
def test_ubn_batch_compiles_at_resnet50_bn_shapes(one_chip, m, c):
    bt = ops._ubn_tile("batch", m, c)
    assert bt is not None and (bt == c or bt % 128 == 0)
    _compile(one_chip,
             lambda x, g, b: ubn_norm(x, g, b, kind="batch", bt=bt,
                                      interpret=False),
             ((m, c), F32), ((c,), F32), ((c,), F32))


def test_ubn_batch_oracle_shapes_are_the_56px_stage():
    """The BN views at 56x56 and above hold no legal tile within the VMEM
    budget, so they take the oracle (counted in ops.ORACLE_ON_TPU)."""
    oracle = [s for s in RESNET50_BN if ops._ubn_tile("batch", *s) is None]
    assert oracle == [s for s in RESNET50_BN if s not in RESNET50_BN_KERNEL]
    assert {m for m, _ in oracle} == {32 * 56 * 56}


def test_ubn_rms_compiles(one_chip):
    bt = ops._ubn_tile("rms", TOK, D)
    assert bt is not None and bt % 8 == 0
    _compile(one_chip,
             lambda x, g: ubn_norm(x, g, None, kind="rms", bt=bt,
                                   interpret=False),
             ((TOK, D), F32), ((D,), F32))


def test_flash_attention_compiles(one_chip):
    b, s, chunk = 1, 512, 128
    assert ops.flash_attention_fits(b, chunk, H, DH, chunk)
    _compile(one_chip,
             lambda q, k, v, qp, kp, kval, qs, ks, vs: flash_attention(
                 q, k, v, qp, kp, kval, qs, ks, vs, causal=True,
                 sm_scale=DH ** -0.5, q_chunk=chunk, kv_chunk=chunk,
                 interpret=False),
             ((b, s, H, DH), I8), ((b, s, KV, DH), I8), ((b, s, KV, DH), I8),
             ((s,), I32), ((s,), I32), ((s,), I32), SCALAR, SCALAR, SCALAR)


def test_paged_attention_compiles(one_chip):
    # the kernel route needs 128-aligned pages (ops.paged_attention_op)
    lanes, pages, page, nb = 8, 64, 128, 4
    assert ops.paged_attention_fits(H, nb * page)
    _compile(one_chip,
             lambda q, kp, vp, t, qp, tv, qs, ks, vs: paged_attention(
                 q, kp, vp, t, qp, tv, qs, ks, vs, sm_scale=DH ** -0.5,
                 interpret=False),
             ((lanes, H, DH), I8), ((pages, page, KV, DH), I8),
             ((pages, page, KV, DH), I8), ((lanes, nb), I32), ((lanes,), I32),
             ((), I32), SCALAR, SCALAR, SCALAR)


def test_page_gather_compiles(one_chip):
    _compile(one_chip, lambda p, t: page_gather(p, t, interpret=False),
             ((64, 16, KV * DH), I8), ((8, 16), I32))


def test_selective_scan_compiles(one_chip):
    # falcon-mamba-7b: d_inner 8192, state 16
    b, s, d, n = 1, 256, 8192, 16
    _compile(one_chip, lambda a, bb, c: selective_scan(a, bb, c,
                                                       interpret=False),
             ((b, s, d, n), F32), ((b, s, d, n), F32), ((b, s, n), F32))


def test_resnet50_bn_shapes_are_the_models(monkeypatch):
    """RESNET50_BN is every shape qbatchnorm sends ubn_norm_op when the
    paper's model runs at batch 32 (traced abstractly on the CPU)."""
    from repro.configs import get
    from repro.core import preset
    from repro.models import build_model

    seen = set()
    real = ops.ubn_norm_op

    def spy(x, *a, **kw):
        seen.add(tuple(x.shape))
        return real(x, *a, **kw)

    monkeypatch.setattr(ops, "ubn_norm_op", spy)
    model = build_model(get("resnet50"), preset("full8", "native"))
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    jax.eval_shape(model.forward, params,
                   jax.ShapeDtypeStruct((32, 224, 224, 3), F32))
    assert sorted(seen) == sorted(RESNET50_BN)
