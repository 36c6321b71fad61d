"""Shared model building blocks: RoPE, GQA attention (chunked / cached),
SwiGLU MLP, initializers — all built on the WAGEUBN quantized ops.

Attention adaptation of the paper's scheme (DESIGN.md §3): QK^T and PV are
activation-activation int8 matmuls (error quantizer = cfg.e_attn, default
QuantSpec("sq", 8)); softmax logits run on the fp32 VPU; probabilities are
quantized onto the k_A grid ([0,1], where direct quantization is exact-range).
"""
from __future__ import annotations

import contextlib
import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core import (QTensor, qact, qdense, qeinsum, qprobs, qrmsnorm,
                        qlayernorm, qt_carrier)
from repro.core import qfuncs as qf
from repro.core.qconfig import QConfig
from repro.kernels import ops as kops

Array = jax.Array

NEG_INF = -1e9


def target_logit(logits, labels):
    """Gather labels' logits WITHOUT all-gathering a vocab-sharded tensor:
    a masked sum partitions cleanly (local mask + tiny (B,S) all-reduce),
    where take_along_axis would gather the full logits to every device."""
    iota = lax.broadcasted_iota(jnp.int32, logits.shape, logits.ndim - 1)
    mask = iota == labels[..., None]
    return jnp.sum(jnp.where(mask, logits, 0.0), axis=-1)


def constrain(mesh, x, spec):
    """Anchor intermediate sharding (3-axis meshes defeat propagation)."""
    if mesh is None:
        return x
    from jax.sharding import NamedSharding
    return lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def maybe_remat(acfg, fn):
    if getattr(acfg, "remat", "full") == "full":
        return jax.checkpoint(fn, prevent_cse=False)
    return fn


# --------------------------------------------------------------------------
# manual tensor parallelism (Megatron f/g pair for shard_map bodies)
# --------------------------------------------------------------------------
#
# Inside the full-manual shard_map training step (launch/train.py) the model
# axis carries head/FFN/expert shards.  A column-sharded matmul needs no
# forward communication but its input cotangent is PARTIAL over the axis
# (each rank only back-propagates through its local output features);
# a row-sharded matmul produces partial outputs.  tp_enter / tp_exit are the
# classic conjugate pair: enter = identity fwd / psum bwd (placed where
# replicated activations feed sharded params), exit = psum fwd / identity
# bwd (placed where partial outputs rejoin the replicated stream).  The
# psums carry fp32 ACTIVATIONS/ERRORS (the TP boundary traffic DESIGN.md §9
# scopes out of the integer-wire gradient contract); parameter gradients
# never cross the model axis — sharded params get local grads, replicated
# params compute identical grads on every rank.


def _psum_float_leaves(axis, ct):
    return jax.tree.map(
        lambda t: t if t.dtype == jax.dtypes.float0 else lax.psum(t, axis),
        ct)


@partial(jax.custom_vjp, nondiff_argnums=(0,))
def tp_enter(axis: str, x):
    """Identity forward; psum over `axis` on the backward cotangent.

    `x` may be an Array or a QTensor (the payload passes through untouched,
    so decompose-once is preserved; only the carrier cotangent is reduced).
    """
    return x


def _tp_enter_fwd(axis, x):
    return x, None


def _tp_enter_bwd(axis, _, ct):
    return (_psum_float_leaves(axis, ct),)


tp_enter.defvjp(_tp_enter_fwd, _tp_enter_bwd)


# Integer-wire TP reduction (serving decode contract, DESIGN.md §12).
# Inside the sharded decode step every tp_exit partial is a sum of int32
# dot products times a SHARED pow2 scale (qeinsum raw outputs and their
# gate-weighted MoE combinations), so the cross-rank reduction can ride an
# integer collective: bitcast the fp32 partials to uint32, all_gather the
# payloads, bitcast back and sum locally.  The local fp32 adds are exact
# (every addend is an exact multiple of the shared scale, well under the
# 2^24 mantissa bound at CPU/test scale), so the result is bitwise equal
# to lax.psum — but the wire carries only integer words, which is what
# tests/test_sharded_serving.py's jaxpr assertion checks.
_TP_INT_WIRE = False


@contextlib.contextmanager
def tp_int_wire():
    """Within this (trace-time) context, tp_exit's forward reduction rides
    an integer all_gather instead of a float psum."""
    global _TP_INT_WIRE
    prev = _TP_INT_WIRE
    _TP_INT_WIRE = True
    try:
        yield
    finally:
        _TP_INT_WIRE = prev


def _wire_reduce(axis: str, y: Array) -> Array:
    if _TP_INT_WIRE and y.dtype == jnp.float32:
        w = lax.all_gather(lax.bitcast_convert_type(y, jnp.uint32), axis)
        return jnp.sum(lax.bitcast_convert_type(w, jnp.float32), axis=0)
    return lax.psum(y, axis)


@partial(jax.custom_vjp, nondiff_argnums=(0,))
def tp_exit(axis: str, y: Array) -> Array:
    """psum over `axis` forward (partial row-sharded outputs -> replicated);
    identity backward (the downstream cotangent is already replicated).
    Under tp_int_wire() the forward reduction is gather-bitcast-sum."""
    return _wire_reduce(axis, y)


def _tp_exit_fwd(axis, y):
    return _wire_reduce(axis, y), None


def _tp_exit_bwd(axis, _, ct):
    return (ct,)


tp_exit.defvjp(_tp_exit_fwd, _tp_exit_bwd)


def _gather_lastdim_impl(axis: str, x: Array) -> Array:
    w = lax.all_gather(lax.bitcast_convert_type(x, jnp.uint32), axis)
    w = lax.bitcast_convert_type(w, x.dtype)          # (tp, ..., local)
    return jnp.moveaxis(w, 0, -2).reshape(*x.shape[:-1], -1)


@partial(jax.custom_vjp, nondiff_argnums=(0,))
def tp_gather_lastdim(axis: str, x: Array) -> Array:
    """Concatenate rank-local last-dim slices into the replicated full axis
    (mamba2's head-sharded y rejoining the replicated norm/gate tail).

    Forward: integer-payload all_gather (bitcast, same wire contract as
    tp_exit) then a transpose/reshape — pure data movement, bitwise exact.
    Backward: each rank keeps its own slice of the cotangent.
    """
    return _gather_lastdim_impl(axis, x)


def _tp_gather_fwd(axis, x):
    return _gather_lastdim_impl(axis, x), x.shape[-1]


def _tp_gather_bwd(axis, local, ct):
    r = lax.axis_index(axis)
    return (lax.dynamic_slice_in_dim(ct, r * local, local, axis=-1),)


tp_gather_lastdim.defvjp(_tp_gather_fwd, _tp_gather_bwd)


def lscan(acfg, body, init, xs):
    """scan-over-layers honoring acfg.unroll_layers (cost-exact compiles)."""
    return lax.scan(body, init, xs, unroll=(True if acfg.unroll_layers
                                            else 1))


# --------------------------------------------------------------------------
# init (paper Eq. 9: MSRA + k_WU-grid discretization)
# --------------------------------------------------------------------------


def winit(cfg: QConfig, key, shape, fan_in: int) -> Array:
    w = jax.random.normal(key, shape, jnp.float32) / math.sqrt(fan_in)
    if cfg.quantize:
        lim = 1.0 - qf.d(cfg.k_wu)
        w = jnp.clip(qf.q_direct(w, cfg.k_wu), -lim, lim)
    return w


# --------------------------------------------------------------------------
# RoPE
# --------------------------------------------------------------------------


def rope(x: Array, pos: Array, theta: float = 1e4) -> Array:
    """x: (..., S, H, dh), pos: (S,) int32."""
    dh = x.shape[-1]
    half = dh // 2
    freqs = jnp.exp(-jnp.arange(0, half, dtype=jnp.float32)
                    * (math.log(theta) / half))
    ang = pos.astype(jnp.float32)[:, None] * freqs[None, :]   # (S, half)
    cos = jnp.cos(ang)[:, None, :]
    sin = jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


# --------------------------------------------------------------------------
# attention
# --------------------------------------------------------------------------


def _attn_scores(cfg, q, k):
    """(B,S,KV,G,dh) x (B,T,KV,dh) -> (B,S,KV,G,T) through qeinsum.

    q/k may be QTensors (e.g. the int8 KV cache in decode): their payloads
    feed the integer dot directly, with no re-decomposition."""
    return qeinsum(cfg, "bskgd,btkd->bskgt", cfg.e_attn, False, q, k)


def _attn_out(cfg, p, v):
    return qeinsum(cfg, "bskgt,btkd->bskgd", cfg.e_attn, False, p, v)


def _payload8(x) -> bool:
    """Single-plane int8 QTensor with a differentiable carrier — what the
    fused attention kernels consume."""
    return (isinstance(x, QTensor) and x.lo is None
            and x.data.dtype == jnp.int8 and x.carrier is not None)


def chunked_attention(cfg: QConfig, q: Array, k: Array, v: Array, *,
                      causal: bool, q_pos: Array, k_pos: Array,
                      q_chunk: int = 1024, kv_chunk: int = 512) -> Array:
    """Memory-efficient online-softmax attention (flash-style).

    q: (B, S, H, dh) on the activation grid; k/v: (B, T, KV, dh).
    Returns (B, S, H, dh) normalized output on the activation grid.

    Native mode with `cfg.fuse_kernels` routes the forward through the
    tiled Pallas flash kernel (kernels/ops.flash_attention_op) — int8
    payloads in, per-chunk decompositions in-register, bit-identical to
    the pure-JAX path below — via custom_vjp whose backward is the vjp of
    the unfused body (the per-chunk qeinsum Q_E2 semantics of Alg. 2 are
    unchanged).  Everything else takes the pure-JAX chunked path.
    """
    if cfg.native and cfg.fuse_kernels and all(map(_payload8, (q, k, v))):
        out = _flash_fused(cfg, causal, min(q_chunk, q.shape[1]),
                           min(kv_chunk, k.shape[1]), q, k, v, q_pos, k_pos)
        return qact(cfg, "none", out)
    return qact(cfg, "none", _chunked_core(
        cfg, q, k, v, causal=causal, q_pos=q_pos, k_pos=k_pos,
        q_chunk=q_chunk, kv_chunk=kv_chunk))


def _chunked_core(cfg: QConfig, q, k, v, *, causal: bool, q_pos: Array,
                  k_pos: Array, q_chunk: int, kv_chunk: int) -> Array:
    """Pure-JAX online-softmax body (pre-Q_A output): the sim-mode path
    and the fused route's vjp ground truth."""
    # the online-softmax rescale math + chunk padding/scanning run on the
    # fp32 grid carriers; QTensor inputs degrade here (differentiably) and
    # the per-chunk qeinsums re-enter the integer path
    q, k, v = qt_carrier(q), qt_carrier(k), qt_carrier(v)
    b, s, h, dh = q.shape
    t, kv = k.shape[1], k.shape[2]
    g = h // kv
    scale = 1.0 / math.sqrt(dh)

    q_chunk = min(q_chunk, s)
    kv_chunk = min(kv_chunk, t)
    # pad sequence dims up to chunk multiples; padded kv slots are masked out
    s_orig = s
    sp = -s % q_chunk
    tp = -t % kv_chunk
    if sp:
        q = jnp.pad(q, ((0, 0), (0, sp), (0, 0), (0, 0)))
        q_pos = jnp.pad(q_pos, (0, sp))
        s += sp
    k_valid = jnp.ones((t,), bool)
    if tp:
        k = jnp.pad(k, ((0, 0), (0, tp), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, tp), (0, 0), (0, 0)))
        k_pos = jnp.pad(k_pos, (0, tp))
        k_valid = jnp.pad(k_valid, (0, tp))
        t += tp
    q = q.reshape(b, s, kv, g, dh)

    nq, nk = s // q_chunk, t // kv_chunk

    kc = k.reshape(b, nk, kv_chunk, kv, dh).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(b, nk, kv_chunk, kv, dh).transpose(1, 0, 2, 3, 4)
    kpc = k_pos.reshape(nk, kv_chunk)
    kvc = k_valid.reshape(nk, kv_chunk)

    def q_block(qi, qp):
        # qi: (B, qc, KV, G, dh); qp: (qc,)
        def kv_step(carry, inp):
            m, l, o = carry
            ki, vi, kp, kval = inp
            sc = _attn_scores(cfg, qi, ki) * scale     # (B,qc,KV,G,kc)
            mask = kval[None, :] if not causal else (
                (qp[:, None] >= kp[None, :]) & kval[None, :])
            sc = jnp.where(mask[None, :, None, None, :], sc, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(sc, axis=-1))
            p = jnp.exp(sc - m_new[..., None])
            p = qprobs(cfg, p)                         # Q_A on probabilities
            alpha = jnp.exp(m - m_new)
            l = l * alpha + jnp.sum(p, axis=-1)
            o = o * alpha[..., None] + _attn_out(cfg, p, vi)
            return (m_new, l, o), None

        m0 = jnp.full(qi.shape[:-1], NEG_INF, jnp.float32)
        l0 = jnp.zeros(qi.shape[:-1], jnp.float32)
        o0 = jnp.zeros(qi.shape, jnp.float32)
        (m, l, o), _ = lax.scan(kv_step, (m0, l0, o0), (kc, vc, kpc, kvc))
        return o / jnp.maximum(l, 1e-9)[..., None]

    qb = q.reshape(b, nq, q_chunk, kv, g, dh).transpose(1, 0, 2, 3, 4, 5)
    qpb = q_pos.reshape(nq, q_chunk)
    out = lax.map(lambda args: q_block(*args), (qb, qpb))
    out = out.transpose(1, 0, 2, 3, 4, 5).reshape(b, s, h, dh)
    return out[:, :s_orig]


@partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3))
def _flash_fused(cfg: QConfig, causal: bool, q_chunk: int, kv_chunk: int,
                 q: QTensor, k: QTensor, v: QTensor, q_pos: Array,
                 k_pos: Array) -> Array:
    """Fused-forward attention: pad payloads to chunk multiples and run the
    tiled Pallas flash kernel.  Bit-identical to `_chunked_core` (the
    kernel re-derives every per-chunk decomposition in-register)."""
    b, s, h, dh = q.shape
    t = k.shape[1]
    sp, tp = -s % q_chunk, -t % kv_chunk
    q8, k8, v8 = q.data, k.data, v.data
    k_valid = jnp.ones((t,), jnp.int32)
    if sp:
        q8 = jnp.pad(q8, ((0, 0), (0, sp), (0, 0), (0, 0)))
        q_pos = jnp.pad(q_pos, (0, sp))
    if tp:
        k8 = jnp.pad(k8, ((0, 0), (0, tp), (0, 0), (0, 0)))
        v8 = jnp.pad(v8, ((0, 0), (0, tp), (0, 0), (0, 0)))
        k_pos = jnp.pad(k_pos, (0, tp))
        k_valid = jnp.pad(k_valid, (0, tp))
    out = kops.flash_attention_op(
        q8, k8, v8, q_pos, k_pos, k_valid, q.scale, k.scale, v.scale,
        causal=causal, sm_scale=1.0 / math.sqrt(dh), q_chunk=q_chunk,
        kv_chunk=kv_chunk, k_a=cfg.k_a)
    return out[:, :s]


def _flash_fused_fwd(cfg, causal, q_chunk, kv_chunk, q, k, v, q_pos, k_pos):
    out = _flash_fused(cfg, causal, q_chunk, kv_chunk, q, k, v, q_pos, k_pos)
    # int8 payload residuals only — the carriers are re-derived in the bwd
    return out, (q.drop_carrier(), k.drop_carrier(), v.drop_carrier(),
                 q_pos, k_pos)


def _flash_fused_bwd(cfg, causal, q_chunk, kv_chunk, res, ct):
    # backward = vjp of the unfused chunked body (per-chunk qeinsums apply
    # Q_E2 per Alg. 2); the fused forward is bit-identical to that body,
    # so this IS the fused op's gradient
    q, k, v, q_pos, k_pos = res
    qw, kw, vw = (t.with_carrier() for t in (q, k, v))
    _, vjp = jax.vjp(
        lambda a, b, c: _chunked_core(cfg, a, b, c, causal=causal,
                                      q_pos=q_pos, k_pos=k_pos,
                                      q_chunk=q_chunk, kv_chunk=kv_chunk),
        qw, kw, vw)
    dq, dk, dv = vjp(ct)
    zero = lambda x: np.zeros(np.shape(x), jax.dtypes.float0)  # noqa: E731
    return dq, dk, dv, zero(q_pos), zero(k_pos)


_flash_fused.defvjp(_flash_fused_fwd, _flash_fused_bwd)


def decode_attention(cfg: QConfig, q, k, v, *,
                     q_pos: Array, t_valid: Array) -> Array:
    """Single-step attention against a full (possibly int8) KV cache.

    q: (B, 1, H, dh); k/v: (B, T, KV, dh) — QTensors straight from the int8
    cache (their payloads feed the integer dots with no dequantize round
    trip) or grid fp32 arrays.  t_valid masks positions >= current length.
    """
    b, s, h, dh = q.shape
    t, kv = k.shape[1], k.shape[2]
    g = h // kv
    scale = 1.0 / math.sqrt(dh)
    qr = q.reshape(b, s, kv, g, dh)
    sc = _attn_scores(cfg, qr, k) * scale              # (B,1,KV,G,T)
    kp = jnp.arange(t)
    mask = (kp[None, :] <= q_pos[:, None]) & (kp[None, :] < t_valid)
    sc = jnp.where(mask[:, None, None, None, :], sc, NEG_INF)
    m = jnp.max(sc, axis=-1, keepdims=True)
    p = jnp.exp(sc - m)
    p = qprobs(cfg, p / jnp.sum(p, axis=-1, keepdims=True))
    out = _attn_out(cfg, p, v).reshape(b, s, h, dh)
    return qact(cfg, "none", out)


def paged_decode_attention(cfg: QConfig, q, k_pages, v_pages, table, k_scale,
                           v_scale, *, q_pos: Array, t_valid: Array) -> Array:
    """Single-step attention against a PAGED int8 KV cache (one layer).

    k_pages/v_pages: (P, page, KV, dh) int8 physical pages; table: (B, NB)
    per-lane page table (logical block -> physical page id, 0 = trash page).

    Native mode with `cfg.fuse_kernels` takes the FUSED route
    (kernels/ops.paged_attention_op): int8 K/V pages stream through VMEM
    behind the scalar-prefetched table and the gathered contiguous KV view
    never exists in HBM — bit-exact against the gather route below, which
    remains for sim mode / non-QTensor queries (and defrag/tests keep the
    standalone page_gather kernel).  Either way everything stays int8 end
    to end: the paged cache is never dequantized or concatenated in fp32.
    """
    b, s, h, dh = q.shape
    if cfg.native and cfg.fuse_kernels and s == 1 and _payload8(q):
        out = kops.paged_attention_op(
            q.data.reshape(b, h, dh), k_pages, v_pages, table, q_pos,
            t_valid, q.scale, k_scale, v_scale,
            sm_scale=1.0 / math.sqrt(dh), k_a=cfg.k_a)
        return qact(cfg, "none", out.reshape(b, s, h, dh))
    from repro.kernels.ops import page_gather_op
    page = k_pages.shape[1]
    nb = table.shape[1]
    k8 = page_gather_op(k_pages, table).reshape(
        b, nb * page, *k_pages.shape[2:])
    v8 = page_gather_op(v_pages, table).reshape(
        b, nb * page, *v_pages.shape[2:])
    return decode_attention(cfg, q, kv_qtensor(k8, k_scale),
                            kv_qtensor(v8, v_scale), q_pos=q_pos,
                            t_valid=t_valid)


def paged_prefill_attention(cfg: QConfig, q, k_pages, v_pages, table,
                            k_scale, v_scale, *, q_pos: Array) -> Array:
    """One PAGE of prefill attention against the paged int8 cache (one
    layer, one lane): the chunked-prefill data path (DESIGN.md §10).

    q: (1, S, H, dh) — S = page_size query tokens of a single lane whose
    KV page was just written into the pool; q_pos: (S,) their absolute
    positions.  k_pages/v_pages: (P, page, KV, dh) int8; table: (1, NB).
    Gathers the lane's pages (the current page included) and applies the
    per-position causal mask — positions beyond q_pos belong to pages not
    yet written this prefill and are masked, so stale arena contents never
    leak in.  Numerics mirror `decode_attention` (normalized probabilities
    onto the k_A grid); every amax spans only this lane's single page, so
    the output is a pure function of (prefix tokens, page tokens) — the
    determinism the radix cache's bitwise-hit contract rests on.
    """
    from repro.kernels.ops import page_gather_op
    b, s, h, dh = q.shape
    page = k_pages.shape[1]
    nb = table.shape[1]
    kv = k_pages.shape[2]
    g = h // kv
    scale = 1.0 / math.sqrt(dh)
    k8 = page_gather_op(k_pages, table).reshape(
        b, nb * page, *k_pages.shape[2:])
    v8 = page_gather_op(v_pages, table).reshape(
        b, nb * page, *v_pages.shape[2:])
    qr = q.reshape(b, s, kv, g, dh)
    sc = _attn_scores(cfg, qr, kv_qtensor(k8, k_scale)) * scale
    kp = jnp.arange(nb * page)                       # (B,S,KV,G,T)
    mask = q_pos[:, None] >= kp[None, :]             # (S, T) causal+valid
    sc = jnp.where(mask[None, :, None, None, :], sc, NEG_INF)
    m = jnp.max(sc, axis=-1, keepdims=True)
    p = jnp.exp(sc - m)
    p = qprobs(cfg, p / jnp.sum(p, axis=-1, keepdims=True))
    out = _attn_out(cfg, p, kv_qtensor(v8, v_scale)).reshape(b, s, h, dh)
    return qact(cfg, "none", out)


# --------------------------------------------------------------------------
# int8 KV cache
# --------------------------------------------------------------------------


def kv_cache_init(n_layers: int, b: int, t: int, kv: int, dh: int):
    """int8 cache + per-layer pow2 scales (paper k_A applied to the cache).

    Stored as flat int8 + scale arrays (checkpoint/pspec friendly); cache
    reads wrap them back into QTensors via `kv_qtensor` so decode matmuls
    consume the payloads directly.
    """
    return {
        "k": jnp.zeros((n_layers, b, t, kv, dh), jnp.int8),
        "v": jnp.zeros((n_layers, b, t, kv, dh), jnp.int8),
        "k_scale": jnp.full((n_layers,), 2.0 ** -7, jnp.float32),
        "v_scale": jnp.full((n_layers,), 2.0 ** -7, jnp.float32),
        "pos": jnp.zeros((b,), jnp.int32),
    }


def kv_quantize(x, step):
    """Payload on the int8 cache grid.  QTensor inputs requantize payload-
    to-payload (a pow2 shift saturating to int8 — NO amax pass); arrays
    take the legacy path."""
    if isinstance(x, QTensor):
        return x.requantize(step, k=8)
    return jnp.clip(jnp.round(x / step), -127, 127).astype(jnp.int8)


def kv_qtensor(x8: Array, step: Array) -> QTensor:
    """Wrap a cache slice as a (non-differentiable) QTensor."""
    return QTensor(x8, step, 8)


def page_scatter_token(pages: Array, table: Array, pos: Array,
                       tok: Array) -> Array:
    """Write one decode step's quantized KV token into its page slot.

    pages: (P, page, KV, dh) int8; table: (B, NB); pos: (B,) the position
    being written; tok: (B, KV, dh) int8.  Lane b lands in
    pages[table[b, pos//page], pos%page].  Dead lanes' table rows are all 0,
    so their writes collide harmlessly on the trash page.
    """
    page = pages.shape[1]
    blk, off = pos // page, pos % page
    pid = jnp.take_along_axis(table, blk[:, None], axis=1)[:, 0]
    return pages.at[pid, off].set(tok)


def page_write(pages: Array, pid: Array, block: Array) -> Array:
    """Whole-page KV write: pages (P, page, KV, dh) <- block (page, KV, dh)
    at physical page `pid`.  The chunked-prefill step processes exactly one
    page-aligned block of positions at a time, so the write is a single
    dense page store (pid 0 = trash page absorbs masked-out chunks)."""
    return pages.at[pid].set(block)


def kv_dequantize(x8: Array, step: Array) -> Array:
    """DEPRECATED: decode paths consume the cache via `kv_qtensor` now (the
    payload feeds the integer dots directly); kept for external callers."""
    return x8.astype(jnp.float32) * step


# --------------------------------------------------------------------------
# MLP
# --------------------------------------------------------------------------


def swiglu(cfg: QConfig, x: Array, w_gate: Array, w_up: Array,
           w_down: Array, act: str = "silu") -> Array:
    gate = qact(cfg, act, qdense(cfg, x, w_gate))
    up = qact(cfg, "none", qdense(cfg, x, w_up))
    h = qact(cfg, "none", gate * up)
    return qdense(cfg, h, w_down)


def mlp(cfg: QConfig, x: Array, w_up: Array, w_down: Array,
        act: str = "gelu") -> Array:
    h = qact(cfg, act, qdense(cfg, x, w_up))
    return qdense(cfg, h, w_down)


def norm(cfg: QConfig, kind: str, x: Array, gamma: Array,
         beta: Array | None = None) -> Array:
    if kind == "rmsnorm":
        return qrmsnorm(cfg, x, gamma)
    return qlayernorm(cfg, x, gamma, beta)
