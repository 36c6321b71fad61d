"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode
(requirement (c): per-kernel allclose against ref.py)."""
import collections
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import quantize, ref
from repro.kernels.backward import bwd_dgrad, bwd_wgrad
from repro.kernels.page_gather import page_gather
from repro.kernels.qmatmul import qmatmul
from repro.kernels.quantize import cq_stochastic, quantize_fused
from repro.kernels.selective_scan import selective_scan
from repro.kernels.ubn import ubn_norm


@pytest.mark.parametrize("m,k,n", [(8, 16, 8), (128, 128, 128),
                                   (256, 512, 128), (100, 130, 70),
                                   (1, 256, 64), (37, 64, 129)])
@pytest.mark.parametrize("blocks", [(32, 32, 64), (128, 128, 128)])
def test_qmatmul_sweep(m, k, n, blocks):
    bm, bn, bk = blocks
    a = jax.random.randint(jax.random.PRNGKey(0), (m, k), -128, 128,
                           jnp.int8)
    b = jax.random.randint(jax.random.PRNGKey(1), (k, n), -128, 128,
                           jnp.int8)
    got = qmatmul(a, b, bm=bm, bn=bn, bk=bk, interpret=True)
    want = ref.qmatmul_ref(a, b)
    assert got.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_qmatmul_int32_accumulation_no_overflow_in_int8_domain():
    # worst case: K * 127 * 127 must accumulate exactly in int32
    k = 1024
    a = jnp.full((8, k), 127, jnp.int8)
    b = jnp.full((k, 8), 127, jnp.int8)
    got = qmatmul(a, b, interpret=True)
    assert int(got[0, 0]) == k * 127 * 127


# shape -> VMEM budget of a block: small budgets tile the (rows, C) view in
# both dims and leave ragged last blocks, which Pallas masks
QUANTIZE_CASES = {
    (16, 16): 20480, (100, 70): 20480, (256, 300): 40960, (1, 8): 20480,
    (37,): quantize.BLOCK_BYTES, (): quantize.BLOCK_BYTES,   # ranks 1, 0
    (3, 70, 300): 20480,
    (3, 7, 7, 64): quantize.BLOCK_BYTES,      # ResNet stage 3's rows at 64
    (2, 56, 56, 64): quantize.BLOCK_BYTES,    # a stage 0 activation
    (3, 3, 64, 128): quantize.BLOCK_BYTES,    # an HWIO weight
    (5, 7, 7, 64): 40960,                     # ragged leading dims
    (7, 9, 33, 130): 40960,                   # ragged rows and lanes
}


@pytest.mark.parametrize("shape", list(QUANTIZE_CASES))
@pytest.mark.parametrize("inv", [128.0, 4.0, 1 / 64.0])
def test_quantize_sweep(shape, inv):
    x = jax.random.normal(jax.random.PRNGKey(0), shape) * 3
    got = quantize_fused(x, jnp.float32(inv),
                         block_bytes=QUANTIZE_CASES[shape], interpret=True)
    want = ref.quantize_ref(x, jnp.float32(inv), 127.0)
    assert got.shape == x.shape and got.dtype == jnp.int8
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("m,c,budget,block", [
    (200704, 64, quantize.BLOCK_BYTES, (6528, 64)),   # 64 lanes pad to 128
    (4608, 512, quantize.BLOCK_BYTES, (1632, 512)),
    (392, 2000, quantize.BLOCK_BYTES, (384, 2000)),   # rows tile by 32
    (1, 12845056, quantize.BLOCK_BYTES, (1, 26112)),  # C tiles by 128
    (147, 64, quantize.BLOCK_BYTES, (147, 64)),       # one whole block
])
def test_quantize_blocks_fit_the_budget(m, c, budget, block):
    br, bc = quantize.quantize_blocks(m, c, 4, budget)
    assert (br, bc) == block
    assert br == m or br % 32 == 0
    assert bc == c or bc % 128 == 0
    padded = (-(-br // 8) * 8 * 4 + -(-br // 32) * 32) * (-(-bc // 128) * 128)
    assert padded <= budget


@pytest.mark.parametrize("shape", [(32, 32), (100, 70)])
@pytest.mark.parametrize("dr", [128.0, 64.0])
def test_cq_stochastic_sweep(shape, dr):
    x = jax.random.normal(jax.random.PRNGKey(0), shape)
    bits = jax.random.bits(jax.random.PRNGKey(1), shape, jnp.uint32)
    got = cq_stochastic(x, bits, jnp.float32(37.0), dr=dr, bm=64, bn=64,
                        interpret=True)
    want = ref.cq_stochastic_ref(x, bits, jnp.float32(37.0), dr)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("b,s,d,n", [(1, 16, 8, 4), (2, 48, 24, 4),
                                     (2, 64, 32, 16), (1, 33, 10, 2)])
def test_selective_scan_sweep(b, s, d, n):
    k = jax.random.PRNGKey(0)
    a = jnp.exp(-jax.random.uniform(k, (b, s, d, n)))
    bb = jax.random.normal(jax.random.PRNGKey(1), (b, s, d, n)) * 0.1
    c = jax.random.normal(jax.random.PRNGKey(2), (b, s, n))
    got = selective_scan(a, bb, c, bd=8, bs=16, interpret=True)
    want = ref.selective_scan_ref(a, bb, c)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


def test_selective_scan_long_dependency():
    """State must persist across seq blocks (VMEM scratch carry)."""
    b, s, d, n = 1, 64, 4, 2
    a = jnp.ones((b, s, d, n)) * 0.99
    bb = jnp.zeros((b, s, d, n)).at[:, 0].set(1.0)   # impulse at t=0
    c = jnp.ones((b, s, n))
    y = selective_scan(a, bb, c, bd=4, bs=8, interpret=True)
    # response at t is n * 0.99^t — nonzero far beyond the first block
    want = n * 0.99 ** jnp.arange(s)
    np.testing.assert_allclose(np.asarray(y[0, :, 0]), np.asarray(want),
                               rtol=1e-4)


@pytest.mark.parametrize("p,page,d,b,nb", [(8, 4, 16, 2, 3), (32, 8, 64, 4, 4),
                                           (5, 2, 8, 1, 5)])
def test_page_gather_sweep(p, page, d, b, nb):
    pages = jax.random.randint(jax.random.PRNGKey(0), (p, page, d),
                               -128, 128, jnp.int8)
    table = jax.random.randint(jax.random.PRNGKey(1), (b, nb), 0, p,
                               jnp.int32)
    got = page_gather(pages, table, interpret=True)
    want = ref.page_gather_ref(pages, table)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_page_gather_clamps_out_of_range():
    """Dead lanes carry id 0 / garbage ids; both must clamp, not wrap."""
    pages = jnp.arange(4 * 2 * 4, dtype=jnp.int8).reshape(4, 2, 4)
    table = jnp.asarray([[-3, 99]], jnp.int32)
    got = page_gather(pages, table, interpret=True)
    np.testing.assert_array_equal(np.asarray(got[0, 0]),
                                  np.asarray(pages[0]))
    np.testing.assert_array_equal(np.asarray(got[0, 1]),
                                  np.asarray(pages[3]))


def test_page_gather_op_dispatch_trailing_dims():
    from repro.kernels import ops
    pages = jax.random.randint(jax.random.PRNGKey(0), (6, 4, 2, 8),
                               -128, 128, jnp.int8)
    table = jax.random.randint(jax.random.PRNGKey(1), (3, 2), 0, 6,
                               jnp.int32)
    got = ops.page_gather_op(pages, table)
    assert got.shape == (3, 2, 4, 2, 8)
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(ref.page_gather_ref(pages, table)))
    got2 = ops.page_gather_op(pages, table, force_kernel=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(got2))


def test_ops_dispatch_cpu_oracle():
    from repro.kernels import ops
    a = jax.random.randint(jax.random.PRNGKey(0), (16, 16), -128, 128,
                           jnp.int8)
    got = ops.qmatmul_op(a, a)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(ref.qmatmul_ref(a, a)))
    got2 = ops.qmatmul_op(a, a, force_kernel=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(got2))


# --------------------------------------------------------------------------
# fused requantize epilogue
# --------------------------------------------------------------------------


@pytest.mark.parametrize("m,k,n", [(16, 32, 16), (37, 70, 19),
                                   (128, 256, 64), (1, 17, 5)])
@pytest.mark.parametrize("inv", [2.0 ** -10, 2.0 ** -6, 2.0 ** -14])
def test_qmatmul_requant_sweep(m, k, n, inv):
    a = jax.random.randint(jax.random.PRNGKey(0), (m, k), -128, 128,
                           jnp.int8)
    b = jax.random.randint(jax.random.PRNGKey(1), (k, n), -128, 128,
                           jnp.int8)
    got = qmatmul(a, b, jnp.float32(inv), bm=32, bn=32, bk=64,
                  interpret=True)
    want = ref.qmatmul_requant_ref(a, b, jnp.float32(inv))
    assert got.dtype == jnp.int8
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_qmatmul_requant_saturates():
    a = jnp.full((8, 64), 127, jnp.int8)
    b = jnp.full((64, 8), 127, jnp.int8)
    got = qmatmul(a, b, jnp.float32(1.0), interpret=True)   # way over range
    assert int(got[0, 0]) == 127 and got.dtype == jnp.int8


# --------------------------------------------------------------------------
# fused-prologue backward kernels (dgrad / wgrad)
# --------------------------------------------------------------------------

_BWD_MODES = [("affine", 8), ("affine", 16), ("flag", 8)]


def _bwd_data(m, k, n, scale=0.3):
    g = jax.random.normal(jax.random.PRNGKey(2), (m, n)) * scale
    w8 = jax.random.randint(jax.random.PRNGKey(3), (k, n), -128, 128,
                            jnp.int8)
    a8 = jax.random.randint(jax.random.PRNGKey(4), (m, k), -128, 128,
                            jnp.int8)
    step = jnp.float32(2.0 ** -9)
    scal = jnp.stack([1.0 / step, step * 2.0 ** -7, step * 2.0 ** -14])
    return g, w8, a8, scal


@pytest.mark.parametrize("m,k,n", [(16, 32, 16), (37, 70, 19), (6, 32, 16),
                                   (128, 128, 128), (1, 13, 33)])
@pytest.mark.parametrize("mode,kb", _BWD_MODES)
def test_bwd_dgrad_sweep(m, k, n, mode, kb):
    g, w8, _, scal = _bwd_data(m, k, n)
    got = bwd_dgrad(g, w8, scal, mode=mode, k=kb, bm=32, bk=32, bn=16,
                    interpret=True)
    want = ref.dgrad_ref(g, w8, scal, mode=mode, k=kb)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("m,k,n", [(16, 32, 16), (37, 70, 19), (6, 32, 16),
                                   (128, 128, 128), (1, 13, 33)])
@pytest.mark.parametrize("mode,kb", _BWD_MODES)
def test_bwd_wgrad_sweep(m, k, n, mode, kb):
    g, _, a8, scal = _bwd_data(m, k, n)
    got = bwd_wgrad(a8, g, scal, mode=mode, k=kb, bm=32, bk=32, bn=16,
                    interpret=True)
    want = ref.wgrad_ref(a8, g, scal, mode=mode, k=kb)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("mode,kb", _BWD_MODES)
def test_bwd_prologue_matches_quantizer_payloads(mode, kb):
    """The kernels' in-prologue quantize must equal Quantizer.quantize —
    the contract that makes the fused route bit-exact vs the legacy path."""
    from repro.core.qtensor import get_quantizer
    g = jax.random.normal(jax.random.PRNGKey(5), (24, 40)) * 0.4
    name = "flag" if mode == "flag" else "sq"
    q = get_quantizer(name, kb)
    plan = q.fused_plan(g)
    assert plan is not None and plan[0] == mode
    steps = plan[1]
    planes = ref.bwd_error_planes_ref(g, 1.0 / steps[0], mode=mode, k=kb)
    want = q.quantize(g).planes()
    assert len(planes) == len(want)
    for got_p, (want_p, _) in zip(planes, want):
        np.testing.assert_array_equal(np.asarray(got_p), np.asarray(want_p))


def test_bwd_ops_dispatch():
    from repro.kernels import ops
    g, w8, a8, scal = _bwd_data(20, 24, 12)
    for mode, kb in _BWD_MODES:
        o = ops.dgrad_op(g, w8, scal, mode=mode, k=kb)
        ok = ops.dgrad_op(g, w8, scal, mode=mode, k=kb, force_kernel=True)
        np.testing.assert_array_equal(np.asarray(o), np.asarray(ok))
        o = ops.wgrad_op(a8, g, scal, mode=mode, k=kb)
        ok = ops.wgrad_op(a8, g, scal, mode=mode, k=kb, force_kernel=True)
        np.testing.assert_array_equal(np.asarray(o), np.asarray(ok))


# --------------------------------------------------------------------------
# fused UBN kernel
# --------------------------------------------------------------------------

_UBN_W = dict(k_mu=16, k_sigma=16, k_bn=16, k_gamma=8, k_beta=8,
              eps=2.0 ** -8)


@pytest.mark.parametrize("m,n", [(16, 32), (33, 48), (100, 24), (1, 8),
                                 (7, 130)])
@pytest.mark.parametrize("kind", ["rms", "layer", "batch"])
def test_ubn_sweep(m, n, kind):
    x = jax.random.normal(jax.random.PRNGKey(0), (m, n)) * 0.5
    gamma = jax.random.normal(jax.random.PRNGKey(1), (n,)) * 0.2 + 1.0
    beta = (None if kind == "rms"
            else jax.random.normal(jax.random.PRNGKey(2), (n,)) * 0.1)
    got = ubn_norm(x, gamma, beta, kind=kind, bt=16, interpret=True,
                   **_UBN_W)
    want = ref.ubn_norm_ref(x, gamma, beta, kind=kind, **_UBN_W)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ResNet-50 batch-norm views (rows, channels) at batch 32, 224 px that take
# the kernel route on a TPU (tests/test_tpu_compile.py compiles them)
@pytest.mark.parametrize("m,c", [(25088, 128), (25088, 256), (25088, 512),
                                 (6272, 256), (6272, 512), (6272, 1024),
                                 (1568, 512), (1568, 2048)])
def test_ubn_batch_resnet50_shapes_bitexact(m, c):
    """Through ops.ubn_norm_op's legal tile, the kernel (interpret mode)
    is bit-identical to the oracle at the paper model's own shapes."""
    from repro.kernels import ops
    x = jax.random.normal(jax.random.PRNGKey(0), (m, c)) * 0.5 + 0.1
    gamma = jax.random.normal(jax.random.PRNGKey(1), (c,)) * 0.2 + 1.0
    beta = jax.random.normal(jax.random.PRNGKey(2), (c,)) * 0.1
    assert ops._ubn_tile("batch", m, c) is not None
    got = ops.ubn_norm_op(x, gamma, beta, kind="batch", force_kernel=True,
                          **_UBN_W)
    want = ref.ubn_norm_ref(x, gamma, beta, kind="batch", **_UBN_W)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_ubn_zero_rows_no_nan():
    """Padded/degenerate rows (all zeros) must normalize to 0, not NaN."""
    x = jnp.zeros((5, 16))
    gamma = jnp.ones((16,))
    for kind in ("rms", "layer", "batch"):
        beta = None if kind == "rms" else jnp.zeros((16,))
        y = ubn_norm(x, gamma, beta, kind=kind, bt=8, interpret=True,
                     **_UBN_W)
        assert not bool(jnp.isnan(y).any())
        np.testing.assert_array_equal(np.asarray(y), 0.0)


def test_ubn_ops_dispatch():
    from repro.kernels import ops
    x = jax.random.normal(jax.random.PRNGKey(0), (12, 20)) * 0.5
    gamma = jnp.ones((20,))
    for kind in ("rms", "layer", "batch"):
        beta = None if kind == "rms" else jnp.zeros((20,))
        o = ops.ubn_norm_op(x, gamma, beta, kind=kind)
        ok = ops.ubn_norm_op(x, gamma, beta, kind=kind, force_kernel=True)
        np.testing.assert_array_equal(np.asarray(o), np.asarray(ok))


def test_dispatch_report_banner():
    from repro.core import preset
    from repro.kernels import ops
    rep = ops.dispatch_report(preset("full8", "native"))
    assert set(rep["ops"]) == set(ops.OPS) and len(ops.OPS) == 10
    assert {"paged_attention", "flash_attention"} <= set(rep["ops"])
    assert rep["fused"] is True and rep["mode"] == "native"
    rep2 = ops.dispatch_report(
        preset("full8", "native").replace(fuse_kernels=False))
    assert rep2["fused"] is False
    banner = ops.dispatch_banner(preset("full8", "native"))
    assert "backend=" in banner and "bwd/ubn=fused" in banner
    assert "attn=fused" in banner
    assert "route=" in ops.dispatch_banner()


# --------------------------------------------------------------------------
# fused paged decode attention / flash attention
# --------------------------------------------------------------------------


def _paged_case(p, page, kv, g, dh, b, nb, seed=0):
    """Pages + a table exercising dead lanes (trash page 0), multi-page
    contexts crossing page boundaries, and ragged last pages."""
    r = np.random.default_rng(seed)
    kp = jnp.asarray(r.integers(-127, 128, (p, page, kv, dh)), jnp.int8)
    vp = jnp.asarray(r.integers(-127, 128, (p, page, kv, dh)), jnp.int8)
    q8 = jnp.asarray(r.integers(-127, 128, (b, kv * g, dh)), jnp.int8)
    table = np.zeros((b, nb), np.int32)
    q_pos = np.zeros((b,), np.int32)
    ids = list(range(1, p))
    for lane in range(1, b):                 # lane 0 stays dead
        n_blk = 1 + (lane % nb)
        take, ids = ids[:n_blk], ids[n_blk:] + ids[:n_blk]
        table[lane, :n_blk] = take
        q_pos[lane] = n_blk * page - 1 - (lane % page)   # ragged last page
    t_valid = int(q_pos.max()) + 1
    return q8, kp, vp, jnp.asarray(table), jnp.asarray(q_pos), t_valid


@pytest.mark.parametrize("p,page,kv,g,dh,b,nb", [
    (9, 4, 1, 1, 8, 2, 2),        # minimal
    (9, 4, 2, 2, 8, 3, 4),        # GQA, multi-page
    (17, 8, 2, 4, 16, 4, 3),      # wider GQA groups, bigger pages
    (9, 4, 4, 1, 8, 2, 2),        # MHA (g == 1)
    (5, 4, 2, 2, 8, 1, 1),        # single grid cell; every lane dead
])
def test_paged_attention_kernel_sweep(p, page, kv, g, dh, b, nb):
    from repro.kernels.paged_attention import paged_attention
    q8, kp, vp, table, q_pos, t_valid = _paged_case(p, page, kv, g, dh,
                                                    b, nb)
    scal = (jnp.float32(2 ** -6), jnp.float32(2 ** -7), jnp.float32(2 ** -7))
    sm = 1.0 / float(np.sqrt(dh))
    want = ref.paged_attention_ref(q8, kp, vp, table, q_pos, t_valid, *scal,
                                   sm_scale=sm)
    got = paged_attention(q8, kp, vp, table, q_pos, t_valid, *scal,
                          sm_scale=sm, interpret=True)
    np.testing.assert_array_equal(np.asarray(want), np.asarray(got))


def test_paged_attention_op_dispatch():
    from repro.kernels import ops
    q8, kp, vp, table, q_pos, t_valid = _paged_case(9, 4, 2, 2, 8, 3, 4)
    scal = (jnp.float32(2 ** -6), jnp.float32(2 ** -7), jnp.float32(2 ** -7))
    sm = 1.0 / float(np.sqrt(8))
    o = ops.paged_attention_op(q8, kp, vp, table, q_pos, t_valid, *scal,
                               sm_scale=sm)
    ok = ops.paged_attention_op(q8, kp, vp, table, q_pos, t_valid, *scal,
                                sm_scale=sm, force_kernel=True)
    np.testing.assert_array_equal(np.asarray(o), np.asarray(ok))
    assert o.shape == (3, 4, 8) and o.dtype == jnp.float32


def test_paged_attention_out_of_range_table_clamps():
    from repro.kernels.paged_attention import paged_attention
    q8, kp, vp, table, q_pos, t_valid = _paged_case(9, 4, 2, 1, 8, 2, 2)
    bad = table.at[1, 0].set(99)          # clamps to the last page
    scal = (jnp.float32(2 ** -6), jnp.float32(2 ** -7), jnp.float32(2 ** -7))
    sm = 1.0 / float(np.sqrt(8))
    want = ref.paged_attention_ref(q8, kp, vp, bad, q_pos, t_valid, *scal,
                                   sm_scale=sm)
    got = paged_attention(q8, kp, vp, bad, q_pos, t_valid, *scal,
                          sm_scale=sm, interpret=True)
    np.testing.assert_array_equal(np.asarray(want), np.asarray(got))


def test_paged_decode_attention_fused_bitexact_vs_gather_route():
    """The model-layer gate: fused streaming route == the page_gather +
    decode_attention composition, bit for bit (same qact epilogue)."""
    from repro.core import preset
    from repro.core.qtensor import QTensor, qt_carrier
    from repro.models import layers as L
    q8, kp, vp, table, q_pos, t_valid = _paged_case(9, 4, 2, 2, 8, 3, 4)
    b, h, dh = q8.shape
    qt = QTensor(q8.reshape(b, 1, h, dh), jnp.float32(2 ** -6), 8,
                 carrier=None)
    qt = qt.with_carrier()
    ks, vs = jnp.float32(2 ** -7), jnp.float32(2 ** -7)

    def run(fused):
        cfg = preset("full8", "native").replace(fuse_kernels=fused)
        out = L.paged_decode_attention(cfg, qt, kp, vp, table, ks, vs,
                                       q_pos=q_pos,
                                       t_valid=jnp.int32(t_valid))
        return np.asarray(qt_carrier(out))

    np.testing.assert_array_equal(run(True), run(False))


@pytest.mark.parametrize("b,s,kv,g,dh,qc,kc", [
    (1, 8, 1, 1, 8, 4, 4),
    (2, 13, 2, 3, 8, 4, 4),       # ragged + GQA
    (2, 16, 2, 2, 16, 8, 4),      # uneven tile sizes
])
def test_flash_attention_kernel_sweep(b, s, kv, g, dh, qc, kc):
    """Kernel vs oracle on payload inputs.  The comparison is
    assert_allclose_fma (an explicit, ULP-derived FMA-contraction budget —
    jaxpr_utils.FMA_ULPS), never a hand-widened rtol: the online-rescale
    mul+add chains are subject to XLA FMA contraction, which interpret-mode
    Pallas and the eagerly-structured oracle may apply differently.  The
    CPU-dispatched route models actually execute is anchored BITWISE to the
    oracle in the same sweep, so the tolerance cannot leak into model
    numbers."""
    from jaxpr_utils import assert_allclose_fma, assert_bitwise_oracle
    from repro.kernels.ops import flash_attention_op
    from repro.kernels.paged_attention import flash_attention
    r = np.random.default_rng(3)
    h = kv * g
    q8 = jnp.asarray(r.integers(-127, 128, (b, s, h, dh)), jnp.int8)
    k8 = jnp.asarray(r.integers(-127, 128, (b, s, kv, dh)), jnp.int8)
    v8 = jnp.asarray(r.integers(-127, 128, (b, s, kv, dh)), jnp.int8)
    sp, tp = -s % qc, -s % kc
    q8 = jnp.pad(q8, ((0, 0), (0, sp), (0, 0), (0, 0)))
    k8 = jnp.pad(k8, ((0, 0), (0, tp), (0, 0), (0, 0)))
    v8 = jnp.pad(v8, ((0, 0), (0, tp), (0, 0), (0, 0)))
    pos = jnp.arange(s)
    qp, kp = jnp.pad(pos, (0, sp)), jnp.pad(pos, (0, tp))
    kval = jnp.pad(jnp.ones((s,), jnp.int32), (0, tp))
    scal = (jnp.float32(2 ** -7),) * 3
    kw = dict(causal=True, sm_scale=1.0 / float(np.sqrt(dh)), q_chunk=qc,
              kv_chunk=kc)
    want = ref.flash_attention_ref(q8, k8, v8, qp, kp, kval, *scal, **kw)
    got = flash_attention(q8, k8, v8, qp, kp, kval, *scal, **kw,
                          interpret=True)
    assert_allclose_fma(want, got)
    # the dispatched (CPU -> oracle) path IS the reference, bit for bit
    assert_bitwise_oracle(flash_attention_op, ref.flash_attention_ref,
                          q8, k8, v8, qp, kp, kval, *scal, **kw)


def test_flash_attention_noncausal_matches_ref():
    from jaxpr_utils import assert_allclose_fma, assert_bitwise_oracle
    from repro.kernels.ops import flash_attention_op
    from repro.kernels.paged_attention import flash_attention
    r = np.random.default_rng(5)
    b, s, kv, g, dh = 2, 8, 2, 1, 8
    q8 = jnp.asarray(r.integers(-127, 128, (b, s, kv * g, dh)), jnp.int8)
    k8 = jnp.asarray(r.integers(-127, 128, (b, s, kv, dh)), jnp.int8)
    v8 = jnp.asarray(r.integers(-127, 128, (b, s, kv, dh)), jnp.int8)
    pos = jnp.arange(s)
    kval = jnp.ones((s,), jnp.int32)
    scal = (jnp.float32(2 ** -7),) * 3
    kw = dict(causal=False, sm_scale=1.0 / float(np.sqrt(dh)), q_chunk=4,
              kv_chunk=4)
    want = ref.flash_attention_ref(q8, k8, v8, pos, pos, kval, *scal, **kw)
    got = flash_attention(q8, k8, v8, pos, pos, kval, *scal, **kw,
                          interpret=True)
    assert_allclose_fma(want, got)
    assert_bitwise_oracle(flash_attention_op, ref.flash_attention_ref,
                          q8, k8, v8, pos, pos, kval, *scal, **kw)


def test_chunked_attention_fused_bitexact_and_grads():
    """Fused flash forward == unfused pure-JAX chunked path bitwise (under
    jit, the way models run it); gradients agree because the fused bwd IS
    the vjp of the unfused body."""
    from repro.core import preset, qact
    from repro.core.qtensor import qt_carrier
    from repro.models import layers as L
    r = np.random.default_rng(7)
    b, s, kv, g, dh = 2, 13, 2, 3, 8
    h = kv * g
    x = jnp.asarray(r.normal(size=(b, s, h, dh)), jnp.float32) * 0.3
    kx = jnp.asarray(r.normal(size=(b, s, kv, dh)), jnp.float32) * 0.3
    vx = jnp.asarray(r.normal(size=(b, s, kv, dh)), jnp.float32) * 0.3
    pos = jnp.arange(s)

    def run(fused, inputs):
        cfg = preset("full8", "native").replace(fuse_kernels=fused)

        def f(x, kx, vx):
            q, k, v = (qact(cfg, "none", t) for t in (x, kx, vx))
            out = L.chunked_attention(cfg, q, k, v, causal=True, q_pos=pos,
                                      k_pos=pos, q_chunk=4, kv_chunk=4)
            return jnp.sum(qt_carrier(out) ** 2)

        val, grads = jax.jit(jax.value_and_grad(f, argnums=(0, 1, 2)))(
            *inputs)
        return val, grads

    vf, gf = run(True, (x, kx, vx))
    vu, gu = run(False, (x, kx, vx))
    assert np.asarray(vf) == np.asarray(vu)
    for a, b_ in zip(gf, gu):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=1e-6, atol=1e-7)


def test_unaligned_attention_takes_counted_oracle_on_tpu(monkeypatch):
    """Under TPU dispatch, shapes whose blocks break the TPU tiling rule
    (4-token pages, 16-wide kv chunks) take the bit-identical oracle, and
    ops.ORACLE_ON_TPU counts each such traced call."""
    from repro.kernels import ops
    q8, kp, vp, table, q_pos, t_valid = _paged_case(9, 4, 2, 2, 8, 3, 4)
    scal = (jnp.float32(2 ** -6), jnp.float32(2 ** -7), jnp.float32(2 ** -7))
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    monkeypatch.setattr(ops, "ORACLE_ON_TPU", collections.Counter())
    jaxpr = jax.make_jaxpr(lambda q: ops.paged_attention_op(
        q, kp, vp, table, q_pos, t_valid, *scal, sm_scale=0.125))(q8)
    assert "pallas_call" not in str(jaxpr)
    qf8 = jnp.zeros((1, 32, 4, 16), jnp.int8)
    kf8 = jnp.zeros((1, 32, 2, 16), jnp.int8)
    pos = jnp.arange(32)
    jaxpr = jax.make_jaxpr(lambda q: ops.flash_attention_op(
        q, kf8, kf8, pos, pos, jnp.ones((32,), jnp.int32), *scal,
        causal=True, sm_scale=0.25, q_chunk=16, kv_chunk=16))(qf8)
    assert "pallas_call" not in str(jaxpr)
    assert ops.ORACLE_ON_TPU == {"paged_attention": 1, "flash_attention": 1}
    assert "oracle_on_tpu=flash_attention:1,paged_attention:1" in \
        ops.dispatch_banner()


def _resnet18_quantize_calls(batch):
    """(minor dim, elements) of every Q_A and Q_W call of one ResNet-18
    forward at 224 px: Q_A after the max-pool and after each block's two
    ReLUs, Q_W of every conv but the float32 stem (basic blocks 2/2/2/2)."""
    calls = [(64, batch * 56 * 56 * 64)]
    h, cin = 56, 64
    for si, cout in enumerate((64, 128, 256, 512)):
        for bi in range(2):
            stride = 2 if si and not bi else 1
            calls += [(cout, 9 * cin * cout), (cout, 9 * cout * cout)]
            if stride != 1 or cin != cout:
                calls.append((cout, cin * cout))
            h, cin = h // stride, cout
            calls += [(cout, batch * h * h * cout)] * 2
    return calls


def test_quantize_views_count_resnet18_forward(monkeypatch):
    """dispatch_report()["quantize_views"] counts one forward's 17 Q_A and
    19 Q_W calls by lane class: the 64-channel stage 0 is "narrow"."""
    from repro.configs import get
    from repro.core import preset
    from repro.kernels import ops
    from repro.models import build_model
    monkeypatch.setattr(ops, "QUANTIZE_VIEWS", collections.Counter())
    model = build_model(get("resnet18"), preset("full8", "native"))
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    jax.eval_shape(model.forward, params,
                   jax.ShapeDtypeStruct((8, 224, 224, 3), jnp.float32))
    calls = _resnet18_quantize_calls(8)
    assert len(calls) == 17 + 19
    want = collections.Counter()
    for c, n in calls:
        lanes = "dense" if c % 128 == 0 else "narrow"
        want[lanes] += 1
        want[f"{lanes}_elements"] += n
    assert want["narrow"] == 5 + 4
    assert ops.dispatch_report()["quantize_views"] == dict(want)
    # the elements quantize_roofline counts are exactly the kernel's
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    monkeypatch.syspath_prepend(root)
    from bench.work import resnet as work
    with open(os.path.join(root, "bench", "configs", "resnet18.json")) as f:
        cfg = json.load(f)
    assert want["dense_elements"] + want["narrow_elements"] == \
        work.quantized_elements(cfg, 8)


def test_fused_decode_jaxpr_streams_pages():
    """Acceptance: with the kernel dispatch forced, the fused decode trace
    contains NO standalone page-gather result and NO dense (B, T, ...) KV
    intermediate outside a pallas body — the gathered cache never exists.
    The unfused trace (contrast) does contain it."""
    from repro.core import preset
    from repro.core.qtensor import QTensor
    from repro.kernels import ops
    from repro.models import layers as L
    # 128-token pages: the TPU kernel route needs lane-aligned pages
    q8, kp, vp, table, q_pos, t_valid = _paged_case(9, 128, 2, 2, 8, 3, 4)
    b, h, dh = q8.shape
    page, kv = kp.shape[1], kp.shape[2]
    nb = table.shape[1]
    qt = QTensor(q8.reshape(b, 1, h, dh), jnp.float32(2 ** -6), 8)
    qt = qt.with_carrier()
    ks, vs = jnp.float32(2 ** -7), jnp.float32(2 ** -7)
    dense = {(b, nb, page, kv, dh), (b, nb * page, kv, dh)}

    def trace(fused):
        from jaxpr_utils import fresh_trace
        cfg = preset("full8", "native").replace(fuse_kernels=fused)
        orig = ops._on_tpu
        ops._on_tpu = lambda: True
        try:
            # fresh_trace: retracing under the patched _on_tpu must not
            # share a cache entry with the unpatched route
            return fresh_trace(
                lambda q: L.paged_decode_attention(
                    cfg, q, kp, vp, table, ks, vs, q_pos=q_pos,
                    t_valid=jnp.int32(t_valid)), qt)
        finally:
            ops._on_tpu = orig

    def dense_kv(jaxpr):
        return [e for e in ops.eqns_outside_pallas(jaxpr.jaxpr)
                if e[1] in dense and e[2] == jnp.int8]

    fused = trace(True)
    assert not dense_kv(fused)
    assert sum(e[0] == "pallas_call"
               for e in ops.eqns_outside_pallas(fused.jaxpr)) >= 2  # 2 passes
    assert dense_kv(trace(False))       # contrast: gather route has it
