"""Quantized compute ops with WAGEUBN backward semantics, QTensor-native.

The paper's dataflow (Fig. 5 / Algorithms 1-2) is realized with three
custom-vjp ops:

  qeinsum  — every matmul.  Operands may be fp32 grid carriers OR QTensors
             (DESIGN.md §2): a QTensor operand is consumed as-is — its int
             payload feeds the integer dot directly, with NO re-decomposition
             (no amax pass) in either the forward or the backward.  Raw fp32
             operands are decomposed exactly once at entry.  Backward: the
             incoming cotangent is quantized with Q_E2 (paper e3) through the
             quantizer registry, then BOTH the input-error dot (e4 = W^T e3)
             and the weight-gradient dot (g_W = e3 x0^T) run on integer
             operands — exactly Algorithm 2.  2-D int8 dots route through
             the Pallas qmatmul kernel (kernels/ops.qmatmul_op).
  qact     — activation + Q_A.  In native mode the output IS a QTensor
             (payload decomposed once, differentiable via its carrier).
             Backward applies Q_E1 (shift quantization) to the cotangent at
             the layer boundary (paper e0), then the activation derivative
             (paper e1) — exactly Algorithm 2.
  qconv    — ResNet convolutions, same error semantics via jax.vjp on the
             saturating conv evaluated at quantized operands.

Weight quantization Q_W (Eq. 10) is applied by callers through `qweight`
(STE, so the gradient reaches the int32 master copy unchanged, Eq. 1);
in native mode it returns a QTensor with the FIXED 2^(1-k_W) scale — no
amax pass ever happens on weights.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from repro.kernels import ops
from repro.kernels.ops import qmatmul_op

from . import qfuncs as qf
from .qconfig import QConfig
from .qtensor import (QTensor, get_quantizer, payload_dtype, qt_carrier,
                      qtensor_cotangent, quantize_ste, resolve_quantizer)

Array = jax.Array


# --------------------------------------------------------------------------
# weight / activation / prob quantizers (forward-path, STE)
# --------------------------------------------------------------------------


def qweight(cfg: QConfig, w: Array):
    """Q_W (Eq. 10) through cfg.w's registered quantizer, STE.

    native mode -> QTensor (fixed-scale int8 payload, decomposed once);
    sim mode    -> fp32 grid carrier (legacy semantics, bit-identical).
    """
    if not cfg.quantize or not cfg.quant_w:
        return w
    quantizer = cfg.w.make()
    with jax.named_scope("qweight"):
        if cfg.native:
            return quantize_ste(quantizer, w)
        return qf.ste(quantizer, w)


def qbn_param(cfg: QConfig, p: Array, k: int) -> Array:
    """Q for norm operands (gamma/beta/mu/sigma, Eq. 13), STE."""
    if not cfg.quantize:
        return p
    return qf.ste(get_quantizer("direct", k), p)


def qprobs(cfg: QConfig, p: Array) -> Array:
    """Attention probabilities onto the k_A grid (in [0,1] so Q is exact-range)."""
    if not cfg.quantize:
        return p
    return qf.ste(get_quantizer("direct", cfg.k_a), p)


_ACT = {
    "relu": (jax.nn.relu, lambda x: (x > 0).astype(jnp.float32)),
    "silu": (jax.nn.silu,
             lambda x: jax.nn.sigmoid(x)
             * (1.0 + x * (1.0 - jax.nn.sigmoid(x)))),
    "gelu": (jax.nn.gelu,
             lambda x: jax.grad(lambda t: jax.nn.gelu(t).sum())(x)),
    "none": (lambda x: x, lambda x: jnp.ones_like(x)),
}


def qact(cfg: QConfig, act: str, x):
    """activation + Q_A.  Native mode returns a QTensor (the int8 payload is
    what downstream matmuls consume); sim/fp32 return fp32 carriers."""
    with jax.named_scope("qact"):
        return _qact(cfg, act, qt_carrier(x))


@partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _qact(cfg: QConfig, act: str, x: Array):
    fn, _ = _ACT[act]
    y = fn(x)
    if cfg.quantize and cfg.quant_a:
        quantizer = cfg.a.make()
        if cfg.native:
            return quantizer.quantize(y).with_carrier()
        return quantizer(y)
    return y


def _qact_fwd(cfg, act, x):
    return _qact(cfg, act, x), x


def _qact_bwd(cfg, act, x, ct):
    _, dfn = _ACT[act]
    g = ct.carrier if isinstance(ct, QTensor) else ct
    if cfg.quantize and cfg.quant_e1:
        with jax.named_scope("q_e1"):
            g = cfg.e1.make()(g)      # Q_E1: e0 = SQ(e4^{l+1})   (Eq. 15)
    return (g * dfn(x),)              # e1 = e0 * dACT            (Alg. 2)


_qact.defvjp(_qact_fwd, _qact_bwd)


# --------------------------------------------------------------------------
# quantized einsum
# --------------------------------------------------------------------------


def _bwd_specs(spec: str):
    ins, out = spec.split("->")
    a_s, b_s = ins.split(",")
    for idx in a_s:
        assert idx in out or idx in b_s, f"unsupported einsum {spec}"
    for idx in b_s:
        assert idx in out or idx in a_s, f"unsupported einsum {spec}"
    return f"{out},{b_s}->{a_s}", f"{a_s},{out}->{b_s}"


def _int_contract(spec, a8, b8):
    """Integer contraction; canonical 2-D forms route through the Pallas
    qmatmul kernel (MXU int8 path), everything else through XLA einsum."""
    if a8.dtype == jnp.int8 and b8.dtype == jnp.int8:
        if spec == "mk,kn->mn":
            return qmatmul_op(a8, b8)
        if spec == "mn,kn->mk":          # da = g @ b^T
            return qmatmul_op(a8, b8.T)
        if spec == "mk,mn->kn":          # db = a^T @ g
            return qmatmul_op(a8.T, b8)
    return jnp.einsum(spec, a8, b8, preferred_element_type=jnp.int32)


def _qt_contract(spec, qa: QTensor, qb: QTensor):
    """Sum of integer dots over the operands' plane products, rescaled."""
    y = None
    for a_data, a_scale in qa.planes():
        for b_data, b_scale in qb.planes():
            t = _int_contract(spec, a_data, b_data).astype(jnp.float32) \
                * (a_scale * b_scale)
            y = t if y is None else y + t
    return y


def _fwd_quantize(cfg: QConfig, x, weight_side: bool) -> QTensor:
    """Native operand entry: QTensors pass through untouched (ZERO redundant
    decomposition); raw carriers are decomposed exactly once."""
    if isinstance(x, QTensor):
        return x.drop_carrier()
    if weight_side and cfg.fixed_w_scale:
        return get_quantizer("clip", cfg.k_w).quantize(x)
    return get_quantizer("grid", cfg.k_w if weight_side else cfg.k_a).quantize(x)


def _error_quantizer(cfg: QConfig, e_kind):
    """Registry lookup for Q_E2: QuantSpec | legacy string | "default"."""
    if cfg.quant_e2:
        quantizer = resolve_quantizer(
            cfg.e2 if e_kind == "default" else e_kind, cfg.k_e2)
        if quantizer.name != "none":
            return quantizer
    # identity ("none" via switch, argument, or spec): no quantization; the
    # native payload falls back to the lossless-on-grid 16-bit decomposition
    # (legacy dec_int16) — NEVER k_e2-wide, which would silently quantize a
    # path explicitly configured as unquantized
    return get_quantizer("none")


def _carrier(cfg, y):
    if cfg.tp_comm_dtype == "bf16":
        return y.astype(jnp.bfloat16).astype(jnp.float32)
    return y


def _tag(x) -> str:
    if isinstance(x, QTensor):
        return "qt" if x.carrier is not None else "qt_frozen"
    return "arr"


def _save(x):
    return x.drop_carrier() if isinstance(x, QTensor) else x


def _wrap_ct(tag: str, saved, d):
    """Cotangent matching the original operand's pytree structure: plain
    array for arrays, QTensor-shaped (gradient on the carrier leaf, float0
    payloads) for QTensors; frozen QTensors (no carrier) get no gradient."""
    if tag == "arr":
        return d
    assert isinstance(saved, QTensor), tag   # _save keeps QTensors QTensors
    ct = qtensor_cotangent(saved, None)
    if tag == "qt":
        ct = dataclasses.replace(ct, carrier=d)
    return ct


def qeinsum(cfg: QConfig, spec: str, e_kind, b_weight: bool, a, b) -> Array:
    """y = einsum(spec, a, b) with WAGEUBN forward/backward quantization.

    `a`/`b`: fp32 grid carriers (via qact/qweight in sim mode) or QTensors
    (native mode) — QTensor payloads feed the integer dots directly.
    `e_kind` selects Q_E2: a QuantSpec, a registered/legacy name ("flag8" |
    "sq16" | "sq8" | "none"), or "default" (cfg.e2).  `b_weight` marks b as
    a saturated Q_W weight (fixed-scale int8 decomposition for raw arrays).
    QTensors without a carrier (e.g. the int8 KV cache) are consumed but
    receive no gradient — they are non-differentiable by construction.
    """
    return _qeinsum(cfg, spec, e_kind, b_weight, _tag(a), _tag(b), a, b)


@partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3, 4, 5))
def _qeinsum(cfg, spec, e_kind, b_weight, a_tag, b_tag, a, b):
    if not cfg.quantize:
        return jnp.einsum(spec, qt_carrier(a), qt_carrier(b))
    if cfg.native:
        qa = _fwd_quantize(cfg, a, False)
        qb = _fwd_quantize(cfg, b, b_weight)
        return _carrier(cfg, _qt_contract(spec, qa, qb))
    return _carrier(cfg, jnp.einsum(spec, qt_carrier(a), qt_carrier(b)))


def _qeinsum_fwd(cfg, spec, e_kind, b_weight, a_tag, b_tag, a, b):
    if not cfg.quantize:
        return jnp.einsum(spec, qt_carrier(a), qt_carrier(b)), \
            (_save(a), _save(b))
    if cfg.native:
        qa = _fwd_quantize(cfg, a, False)
        qb = _fwd_quantize(cfg, b, b_weight)
        y = _carrier(cfg, _qt_contract(spec, qa, qb))
        # int payload residuals: the paper's 4x activation-memory saving
        return y, (qa, qb)
    return _carrier(cfg, jnp.einsum(spec, qt_carrier(a), qt_carrier(b))), \
        (_save(a), _save(b))


def _fusable_operand(q) -> bool:
    return (isinstance(q, QTensor) and q.lo is None
            and q.data.dtype == jnp.int8 and q.data.ndim == 2)


def _fused_bwd(cfg, spec, quantizer, g, a_s, b_s, want_a, want_b):
    """Fused-prologue backward route (DESIGN.md §8), or None to fall back.

    For the canonical 2-D spec with single-plane int8 residuals, Q_E2 is
    fused into the dgrad/wgrad matmul prologues: only the quantizer's scale
    reduction (at most ONE amax, shared by both dots) runs here — the error
    payload is emitted inside the kernels and never materialized.  Output
    is bit-identical to quantizer.quantize + _qt_contract.
    """
    if not (cfg.fuse_kernels and spec == "mk,kn->mn"
            and not isinstance(g, QTensor) and g.ndim == 2):
        return None
    if (want_a and not _fusable_operand(b_s)) or \
            (want_b and not _fusable_operand(a_s)):
        return None
    plan = quantizer.fused_plan(g)
    if plan is None:
        return None
    mode, steps, k = plan
    inv = jnp.float32(1.0) / steps[0]          # pow2: exact reciprocal
    s2 = steps[1] if len(steps) > 1 else jnp.float32(0.0)
    da = db = None
    if want_a:    # e4 = W^T e3, Q_E2 in the kernel prologue (Alg. 2)
        scal = jnp.stack([inv, steps[0] * b_s.scale, s2 * b_s.scale])
        da = ops.dgrad_op(g, b_s.data, scal, mode=mode, k=k)
    if want_b:    # g_W = e3 x0^T, same fused prologue (Alg. 2)
        scal = jnp.stack([inv, steps[0] * a_s.scale, s2 * a_s.scale])
        db = ops.wgrad_op(a_s.data, g, scal, mode=mode, k=k)
    return da, db


def _qeinsum_bwd(cfg, spec, e_kind, b_weight, a_tag, b_tag, res, g):
    da_spec, db_spec = _bwd_specs(spec)
    a_s, b_s = res
    want_a = a_tag != "qt_frozen"
    want_b = b_tag != "qt_frozen"

    if not cfg.quantize:
        da = jnp.einsum(da_spec, g, qt_carrier(b_s)) if want_a else None
        db = jnp.einsum(db_spec, qt_carrier(a_s), g) if want_b else None
        return _wrap_ct(a_tag, a_s, da), _wrap_ct(b_tag, b_s, db)

    quantizer = _error_quantizer(cfg, e_kind)
    if cfg.native:
        fused = _fused_bwd(cfg, spec, quantizer, g, a_s, b_s, want_a, want_b)
        if fused is not None:
            da, db = fused
            return _wrap_ct(a_tag, a_s, da), _wrap_ct(b_tag, b_s, db)
        gq = quantizer.quantize(g)     # e3 = Q_E2(e2), decomposed once
        da = db = None
        if want_a:
            # e4 = W^T e3 on integer operands (Alg. 2)
            da = _qt_contract(da_spec, gq, b_s)
        if want_b:
            # g_W = e3 x0^T on integer operands (Alg. 2)
            db = _qt_contract(db_spec, a_s, gq)
        return _wrap_ct(a_tag, a_s, da), _wrap_ct(b_tag, b_s, db)

    eq = quantizer(g)
    da = jnp.einsum(da_spec, eq, qt_carrier(b_s)) if want_a else None
    db = jnp.einsum(db_spec, qt_carrier(a_s), eq) if want_b else None
    return _wrap_ct(a_tag, a_s, da), _wrap_ct(b_tag, b_s, db)


_qeinsum.defvjp(_qeinsum_fwd, _qeinsum_bwd)


def qdense(cfg: QConfig, x, w: Array, e_kind="default") -> Array:
    """x @ Q_W(w): the Conv step of Alg. 1 for matmul architectures.

    x: (..., K) on the activation grid (Array or QTensor); w: (K, N) master
    weights.  The 2-D contraction routes through the Pallas int8 kernel.
    """
    wq = qweight(cfg, w)
    xm = x.reshape((-1, x.shape[-1]))
    y = qeinsum(cfg, "mk,kn->mn", e_kind, True, xm, wq)
    return y.reshape(x.shape[:-1] + (w.shape[-1],))


def qdense_requant(cfg: QConfig, x, w: Array, step, k: int = 8) -> QTensor:
    """Forward-only qdense emitting the payload on a FIXED pow2 `step`.

    The serving-side entry to the fused requantize epilogue (DESIGN.md §8):
    in native mode with single-plane int8 operands the Pallas matmul's
    epilogue performs int32 accumulate -> pow2 rescale -> round -> clip and
    writes the int8 payload directly — no fp32 carrier, no separate
    quantize pass.  Other modes fall back to qdense + requantize, which is
    bit-identical (every rescale is an exact pow2 scaling).

    x: (..., K) activation (Array or QTensor); w: (K, N) master weights;
    `step` must be a known power of two (e.g. the KV pool's 2^-7).
    Returns a carrier-less QTensor (non-differentiable by construction).
    """
    step = jnp.asarray(step, jnp.float32)
    lim = 2.0 ** (k - 1) - 1.0
    out_shape = x.shape[:-1] + (w.shape[-1],)
    if cfg.quantize and cfg.native and cfg.fuse_kernels and k <= 8:
        wq = qweight(cfg, w)
        xm = x.reshape((-1, x.shape[-1]))
        qa = _fwd_quantize(cfg, xm, False)
        qb = _fwd_quantize(cfg, wq, True)
        if _fusable_operand(qa) and _fusable_operand(qb):
            inv = qa.scale * qb.scale / step     # all pow2: exact
            data = ops.qmatmul_op(qa.data, qb.data, inv, lim=lim)
            return QTensor(data.reshape(out_shape), step, k)
    y = lax.stop_gradient(qt_carrier(qdense(cfg, x, w)))
    data = jnp.clip(jnp.round(y / step), -lim, lim).astype(payload_dtype(k))
    return QTensor(data, step, k)


# --------------------------------------------------------------------------
# quantized convolution (ResNet reproduction)
# --------------------------------------------------------------------------


def _conv(x, w, stride, padding):
    return lax.conv_general_dilated(
        x, w, window_strides=(stride, stride), padding=padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def qconv(cfg: QConfig, x, wq, stride: int, padding: str) -> Array:
    """Quantized conv: operands on grid; backward errors through Q_E2.

    Conv arithmetic runs on exact grid values in fp32 (integer-identical;
    see DESIGN.md §3 — XLA's int8 conv path is TPU-only, so the carrier is
    fp32 while the *semantics* are fixed-point).  QTensor operands
    contribute their differentiable carriers.  The backward rule's ops
    inherit the "qconv" scope (as `transpose(jvp(qconv))`).
    """
    with jax.named_scope("qconv"):
        return _qconv(cfg, qt_carrier(x), qt_carrier(wq), stride, padding)


@partial(jax.custom_vjp, nondiff_argnums=(0, 3, 4))
def _qconv(cfg: QConfig, x: Array, wq: Array, stride: int,
           padding: str) -> Array:
    return _conv(x, wq, stride, padding)


def _qconv_fwd(cfg, x, wq, stride, padding):
    y, vjp = jax.vjp(lambda t, v: _conv(t, v, stride, padding), x, wq)
    return y, vjp


def _qconv_bwd(cfg, stride, padding, vjp, g):
    if cfg.quantize and cfg.quant_e2:
        with jax.named_scope("q_e2"):
            quantizer = cfg.e2.make()
            plan = (quantizer.fused_plan(g)
                    if cfg.native and cfg.fuse_kernels else None)
            if plan is not None and plan[0] == "affine" and plan[2] <= 8 \
                    and quantizer.name != "none":
                # single-plane int8 formats decompose through the fused
                # quantize kernel dispatch (quantize_op), so e3 materializes
                # once as its int8 payload; the conv vjp consumes the grid
                # value (== the legacy fp32 formula bit-exactly, per the
                # registry invariant).  Multi-plane (flag) and wide formats
                # keep the one-pass legacy formula — decomposing them here
                # would add passes, not remove them.
                g = quantizer.quantize(g).dequantize()
            else:
                g = quantizer(g)           # e3 = Q_E2(...)
    return vjp(g)


_qconv.defvjp(_qconv_fwd, _qconv_bwd)
