"""Pallas TPU kernels: fused int8 attention over the paged KV cache.

Two kernel families share one epilogue contract (integer q·k and p·v dots,
Q_A-grid probabilities, pow2 rescales in-register):

paged_attention — the serving DECODE hot loop.  The per-lane page table is
a scalar-prefetch operand (same contract as kernels/page_gather.py): each
(lane, block) grid cell DMAs exactly one int8 K/V page HBM->VMEM, so the
gathered contiguous KV view never exists in HBM.  Two streaming passes:

  pass 1  streams K pages, builds the masked score row in VMEM scratch,
          emits the per-row softmax max `m` and sum `l` (B, H) — int32
          q·k accumulation, one pow2 rescale, fp32 VPU softmax stats.
  glue    the SINGLE probability amax: the unfused path's GridQuantizer
          takes one batch-global amax over the normalized probabilities;
          max(p) per row is exactly 1/l, so the scale is a scalar
          reduction over `l` — it lives BETWEEN the passes, matching the
          training kernels' contract that scale reductions stay outside
          kernel bodies (DESIGN.md §8).
  pass 2  streams K and V pages, recomputes scores in-register, quantizes
          probabilities onto the Q_A grid at the glued scale, and
          accumulates p·v in int32 VMEM scratch; only the (B, H, dh)
          output is written.

flash_attention — the PREFILL/TRAINING tiled online-softmax kernel.  Each
(q-tile, kv-tile) grid cell re-derives the per-chunk GridQuantizer
decompositions in-register (amax over the full batch block — tiles carry
the whole batch so the chunk amaxes match the unfused qeinsum bit-for-bit,
including the saturate-at-pow2-amax corner), quantizes unnormalized
probabilities per kv step, and keeps m/l/o in VMEM scratch across the
sequential kv grid dimension.

Both are bit-exact against kernels/ref.py oracles, which are themselves
operation-for-operation the unfused model compositions — validated in
interpret mode (this container is CPU-only; TPU is the compile target).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# the same decomposition formulas run in-register here and in the XLA
# oracles — one definition keeps the kernel-vs-oracle bit-exactness
# contract in one place
from .ref import NEG_INF, _grid_decompose, _grid_payload, _grid_step

# the flash kernel keeps full-batch (b, h, q_chunk, .) m / l / acc scratch
# and double-buffered q and output blocks in VMEM; ops.flash_attention_fits
# keeps the shapes that take the kernel within this limit
FLASH_VMEM_LIMIT = 64 * 2 ** 20


# --------------------------------------------------------------------------
# paged decode attention
# --------------------------------------------------------------------------


def _page_scores(q, kpage, kq, sm_scale, qpos, tval, j, page, kv, g):
    """Masked f32 score block (H, page) for one lane x one page: integer
    q·k per kv head, pow2 rescale, softmax scale, position mask."""
    rows = []
    for h in range(kv):
        acc = jnp.dot(q[h * g:(h + 1) * g], kpage[:, h, :].T,
                      preferred_element_type=jnp.int32)      # (g, page)
        rows.append(acc)
    sc = jnp.concatenate(rows, axis=0).astype(jnp.float32) * kq * sm_scale
    pos = j * page + jax.lax.broadcasted_iota(jnp.int32, (1, page), 1)
    ok = (pos <= qpos) & (pos < tval)
    return jnp.where(ok, sc, NEG_INF)


def _decode_ml_kernel(table_ref, qpos_ref, tval_ref, q_ref, k_ref, kq_ref,
                      m_ref, l_ref, sc_ref, *, page, kv, g, nb,
                      sm_scale):
    i, j = pl.program_id(0), pl.program_id(1)
    sc = _page_scores(q_ref[0], k_ref[0], kq_ref[0, 0], sm_scale,
                      qpos_ref[i], tval_ref[0], j, page, kv, g)
    sc_ref[:, pl.dslice(j * page, page)] = sc

    @pl.when(j == nb - 1)
    def _reduce():
        # one max + one full-axis sum over the VMEM score row — the same
        # single reductions the unfused softmax runs
        m = jnp.max(sc_ref[...], axis=-1, keepdims=True)
        m_ref[0] = m
        l_ref[0] = jnp.sum(jnp.exp(sc_ref[...] - m), axis=-1, keepdims=True)


def _decode_out_kernel(table_ref, qpos_ref, tval_ref, q_ref, k_ref, v_ref,
                       kq_ref, m_ref, l_ref, pinv_ref, pv_ref, o_ref,
                       acc_ref, *, page, kv, g, nb, sm_scale, k_a):
    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    sc = _page_scores(q_ref[0], k_ref[0], kq_ref[0, 0], sm_scale,
                      qpos_ref[i], tval_ref[0], j, page, kv, g)
    p = jnp.exp(sc - m_ref[0]) / l_ref[0]
    s_ = 2.0 ** (k_a - 1)
    pg = jnp.round(p * s_) / s_                     # qprobs (Q_A grid)
    lim = s_ - 1.0
    p8 = jnp.clip(jnp.round(pg * pinv_ref[0, 0]), -lim,
                  lim).astype(jnp.int8)             # glued single-amax scale
    vpage = v_ref[0]
    for h in range(kv):
        acc_ref[h * g:(h + 1) * g] += jnp.dot(
            p8[h * g:(h + 1) * g], vpage[:, h, :],
            preferred_element_type=jnp.int32)       # (g, dh) int32

    @pl.when(j == nb - 1)
    def _flush():
        o_ref[0] = acc_ref[...].astype(jnp.float32) * pv_ref[0, 0]


@functools.partial(jax.jit, static_argnames=("sm_scale", "k_a", "ds",
                                             "interpret"))
def paged_attention(q8: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                    table: jax.Array, q_pos: jax.Array, t_valid,
                    q_scale, k_scale, v_scale, *, sm_scale: float,
                    k_a: int = 8,
                    ds: tuple = ("parallel", "arbitrary"),
                    interpret: bool = True) -> jax.Array:
    """Fused paged decode attention (two streaming passes + scalar glue).

    q8: (B, H, dh) int8 query payload; k_pages/v_pages: (P, page, KV, dh)
    int8 arenas; table: (B, NB) int32 page ids (clamped; 0 = trash page);
    q_pos: (B,) int32; t_valid: scalar; scales: pow2 payload scales;
    sm_scale: 1/sqrt(dh); ds: dimension_semantics scheduling hint for the
    TPU pipeliner (autotuned — numerics-neutral, unlike the page size).
    Returns (B, H, dh) f32, bit-exact against ref.paged_attention_ref
    (== the unfused gather-then-attend path).
    """
    p_cnt, page, kv, dh = k_pages.shape
    b, kvg = q8.shape[:2]
    g = kvg // kv
    nb = table.shape[1]
    table = jnp.clip(table, 0, p_cnt - 1).astype(jnp.int32)
    qpos = q_pos.astype(jnp.int32)
    tval = jnp.asarray(t_valid, jnp.int32).reshape(1)
    kq = jnp.asarray(q_scale * k_scale, jnp.float32).reshape(1, 1)

    params = pltpu.CompilerParams(dimension_semantics=tuple(ds))
    qspec = pl.BlockSpec((1, kvg, dh), lambda i, j, *_: (i, 0, 0))
    pagespec = pl.BlockSpec((1, page, kv, dh),
                            lambda i, j, tref, *_: (tref[i, j], 0, 0, 0))
    sspec = pl.BlockSpec((1, 1), lambda i, j, *_: (0, 0))
    # per-row m / l as (B, H, 1) columns: a (1, H) block of a (B, H) array
    # breaks the TPU tiling rule, a (1, H, 1) block spans its last two dims
    rowspec = pl.BlockSpec((1, kvg, 1), lambda i, j, *_: (i, 0, 0))

    m, l = pl.pallas_call(
        functools.partial(_decode_ml_kernel, page=page, kv=kv, g=g, nb=nb,
                          sm_scale=sm_scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b, nb),
            in_specs=[qspec, pagespec, sspec],
            out_specs=[rowspec, rowspec],
            scratch_shapes=[pltpu.VMEM((kvg, nb * page), jnp.float32)],
        ),
        out_shape=[jax.ShapeDtypeStruct((b, kvg, 1), jnp.float32)] * 2,
        compiler_params=params,
        name="paged_attention",
        interpret=interpret,
    )(table, qpos, tval, q8, k_pages, kq)

    # the single probability amax: max(p) per row is exp(0)/l == 1.0/l, so
    # the batch-global GridQuantizer scale of the quantized probabilities
    # reduces over `l` alone — a scalar reduction between the passes
    s_ = 2.0 ** (k_a - 1)
    amax_pg = jnp.round(jnp.max(1.0 / l) * s_) / s_
    step = _grid_step(amax_pg, k_a)
    pinv = (jnp.float32(1.0) / step).reshape(1, 1)
    pv = (step * v_scale).reshape(1, 1).astype(jnp.float32)

    return pl.pallas_call(
        functools.partial(_decode_out_kernel, page=page, kv=kv, g=g, nb=nb,
                          sm_scale=sm_scale, k_a=k_a),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b, nb),
            in_specs=[qspec, pagespec, pagespec, sspec, rowspec, rowspec,
                      sspec, sspec],
            out_specs=pl.BlockSpec((1, kvg, dh), lambda i, j, *_: (i, 0, 0)),
            scratch_shapes=[pltpu.VMEM((kvg, dh), jnp.int32)],
        ),
        out_shape=jax.ShapeDtypeStruct((b, kvg, dh), jnp.float32),
        compiler_params=params,
        name="paged_attention",
        interpret=interpret,
    )(table, qpos, tval, q8, k_pages, v_pages, kq, m, l, pinv, pv)


# --------------------------------------------------------------------------
# flash attention (prefill / training forward)
# --------------------------------------------------------------------------


def _flash_kernel(q_ref, k_ref, v_ref, qp_ref, kp_ref, kval_ref, qs_ref,
                  ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref, *, b, h, g,
                  causal, sm_scale, k_a):
    """One (q-chunk, kv-chunk) cell over head-major blocks: q (b, h, qc,
    dh), k/v (b, kv, kc, dh), m/l (b, h, qc, 1).  Every dot and softmax
    step runs on a 2-D (rows, lanes) tile per (batch, head), the layout
    Mosaic lowers; the GridQuantizer amaxes still span the full block."""
    ik = pl.program_id(1)
    s_ = 2.0 ** (k_a - 1)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # per-chunk GridQuantizer decompositions, amax over the FULL batch
    # block — bit-identical to the unfused per-chunk qeinsum entries
    q8, q_step = _grid_decompose(q_ref[...].astype(jnp.float32)
                                 * qs_ref[0, 0], k_a)
    k8, k_step = _grid_decompose(k_ref[...].astype(jnp.float32)
                                 * ks_ref[0, 0], k_a)
    v8, v_step = _grid_decompose(v_ref[...].astype(jnp.float32)
                                 * vs_ref[0, 0], k_a)
    kval = kval_ref[...] != 0                                   # (1, kc)
    mask = kval if not causal else (qp_ref[...] >= kp_ref[...]) & kval
    heads = [(bi, hi) for bi in range(b) for hi in range(h)]

    probs, m_new = {}, {}
    for bi, hi in heads:
        sc = jnp.dot(q8[bi, hi], k8[bi, hi // g].T,
                     preferred_element_type=jnp.int32)          # (qc, kc)
        sc = sc.astype(jnp.float32) * (q_step * k_step) * sm_scale
        sc = jnp.where(mask, sc, NEG_INF)
        m_new[bi, hi] = jnp.maximum(m_ref[bi, hi],
                                    jnp.max(sc, axis=-1, keepdims=True))
        p = jnp.exp(sc - m_new[bi, hi])
        probs[bi, hi] = jnp.round(p * s_) / s_      # qprobs, unnormalized
    # ONE amax over every head's probabilities, as the full-block
    # decomposition takes it
    p_amax = functools.reduce(jnp.maximum, [jnp.max(jnp.abs(p))
                                            for p in probs.values()])
    p_step = _grid_step(p_amax, k_a)
    for bi, hi in heads:
        p8 = _grid_payload(probs[bi, hi], p_step, k_a)
        pv = jnp.dot(p8, v8[bi, hi // g], preferred_element_type=jnp.int32)
        pv = pv.astype(jnp.float32) * (p_step * v_step)          # (qc, dh)
        alpha = jnp.exp(m_ref[bi, hi] - m_new[bi, hi])
        l_ref[bi, hi] = l_ref[bi, hi] * alpha + jnp.sum(
            probs[bi, hi], axis=-1, keepdims=True)
        acc_ref[bi, hi] = acc_ref[bi, hi] * alpha + pv
        m_ref[bi, hi] = m_new[bi, hi]

    @pl.when(ik == pl.num_programs(1) - 1)
    def _flush():
        o_ref[...] = acc_ref[...] / jnp.maximum(l_ref[...], 1e-9)


@functools.partial(jax.jit, static_argnames=("causal", "sm_scale", "q_chunk",
                                             "kv_chunk", "k_a", "ds",
                                             "interpret"))
def flash_attention(q8: jax.Array, k8: jax.Array, v8: jax.Array,
                    q_pos: jax.Array, k_pos: jax.Array, k_valid: jax.Array,
                    q_scale, k_scale, v_scale, *, causal: bool,
                    sm_scale: float, q_chunk: int, kv_chunk: int,
                    k_a: int = 8, ds: tuple = ("parallel", "arbitrary"),
                    interpret: bool = True) -> jax.Array:
    """Tiled online-softmax attention on int8 payloads (fwd only).

    q8: (B, S, H, dh) int8; k8/v8: (B, T, KV, dh) int8 — pre-padded to
    chunk multiples; q_pos (S,) / k_pos (T,) int32; k_valid (T,) int32
    mask of real kv slots.  Returns (B, S, H, dh) f32 pre-Q_A output,
    bit-exact against ref.flash_attention_ref (== the pure-JAX chunked
    online-softmax path in models/layers.py).
    """
    b, s, h, dh = q8.shape
    t, kv = k8.shape[1], k8.shape[2]
    nq, nk = s // q_chunk, t // kv_chunk
    # head-major operands (B, heads, len, dh): each kernel tile is then a
    # (len, dh) matrix; positions ride as a (S, 1) column and (1, T) rows
    qh, kh, vh = (x.transpose(0, 2, 1, 3) for x in (q8, k8, v8))
    qpos = q_pos.astype(jnp.int32).reshape(s, 1)
    kpos = k_pos.astype(jnp.int32).reshape(1, t)
    kval = k_valid.astype(jnp.int32).reshape(1, t)
    scal = [jnp.asarray(v, jnp.float32).reshape(1, 1)
            for v in (q_scale, k_scale, v_scale)]

    sspec = pl.BlockSpec((1, 1), lambda iq, ik: (0, 0))
    qspec = pl.BlockSpec((b, h, q_chunk, dh), lambda iq, ik: (0, 0, iq, 0))
    kvspec = pl.BlockSpec((b, kv, kv_chunk, dh),
                          lambda iq, ik: (0, 0, ik, 0))
    krow = pl.BlockSpec((1, kv_chunk), lambda iq, ik: (0, ik))
    out = pl.pallas_call(
        functools.partial(_flash_kernel, b=b, h=h, g=h // kv,
                          causal=causal, sm_scale=sm_scale, k_a=k_a),
        grid=(nq, nk),
        in_specs=[qspec, kvspec, kvspec,
                  pl.BlockSpec((q_chunk, 1), lambda iq, ik: (iq, 0)),
                  krow, krow, sspec, sspec, sspec],
        out_specs=qspec,
        out_shape=jax.ShapeDtypeStruct((b, h, s, dh), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((b, h, q_chunk, 1), jnp.float32),
            pltpu.VMEM((b, h, q_chunk, 1), jnp.float32),
            pltpu.VMEM((b, h, q_chunk, dh), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=tuple(ds), vmem_limit_bytes=FLASH_VMEM_LIMIT),
        name="flash_attention",
        interpret=interpret,
    )(qh, kh, vh, qpos, kpos, kval, *scal)
    return out.transpose(0, 2, 1, 3)
