"""Pure-jnp oracles for every Pallas kernel (the allclose ground truth)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def qmatmul_ref(a8: jax.Array, b8: jax.Array) -> jax.Array:
    """int8 (M,K) x int8 (K,N) -> int32 (M,N)."""
    return jnp.dot(a8, b8, preferred_element_type=jnp.int32)


def qmatmul_requant_ref(a8: jax.Array, b8: jax.Array, inv: jax.Array,
                        lim: float = 127.0) -> jax.Array:
    """Fused-epilogue matmul: clip(round(int_dot * inv), +-lim) int8.

    inv is the combined pow2 rescale a_scale * b_scale / out_step — the
    epilogue of kernels/qmatmul.qmatmul(requant_inv=...).
    """
    acc = qmatmul_ref(a8, b8).astype(jnp.float32)
    return jnp.clip(jnp.round(acc * inv), -lim, lim).astype(jnp.int8)


def bwd_error_planes_ref(g: jax.Array, inv: jax.Array, *, mode: str,
                         k: int) -> tuple:
    """Q_E payload plane(s) of an error tensor — the fused-prologue formula.

    "affine": one clip(round(g*inv), +-lim) plane (int8 for k<=8 else
    int16); "flag": the two disjoint-support int8 planes of Eq. 17.
    Bit-identical to the matching Quantizer.quantize payloads.
    """
    lim = 2.0 ** (k - 1) - 1.0
    dt = jnp.int8 if k <= 8 else jnp.int16
    if mode == "affine":
        return (jnp.clip(jnp.round(g * inv), -lim, lim).astype(dt),)
    assert mode == "flag", mode
    n = g * inv
    nlo = jnp.round(n * 2.0 ** (k - 1))
    isbig = (jnp.abs(n) >= 1.0) | (jnp.abs(nlo) >= 2.0 ** (k - 1))
    hi = jnp.where(isbig, jnp.clip(jnp.round(n), -lim, lim), 0.0)
    lo = jnp.where(isbig, 0.0, jnp.clip(nlo, -lim, lim))
    return (hi.astype(dt), lo.astype(dt))


def dgrad_ref(g: jax.Array, b8: jax.Array, scal: jax.Array, *, mode: str,
              k: int) -> jax.Array:
    """da (M,K) = sum_planes einsum('mn,kn->mk', Qe(g), b8)_int32 * s_plane.

    scal: (3,) f32 [inv, s1, s2] as in kernels/backward.bwd_dgrad.
    """
    planes = bwd_error_planes_ref(g, scal[0], mode=mode, k=k)
    y = None
    for q, s in zip(planes, (scal[1], scal[2])):
        t = jnp.einsum("mn,kn->mk", q, b8,
                       preferred_element_type=jnp.int32).astype(jnp.float32) \
            * s
        y = t if y is None else y + t
    return y


def wgrad_ref(a8: jax.Array, g: jax.Array, scal: jax.Array, *, mode: str,
              k: int) -> jax.Array:
    """db (K,N) = sum_planes einsum('mk,mn->kn', a8, Qe(g))_int32 * s_plane."""
    planes = bwd_error_planes_ref(g, scal[0], mode=mode, k=k)
    y = None
    for q, s in zip(planes, (scal[1], scal[2])):
        t = jnp.einsum("mk,mn->kn", a8, q,
                       preferred_element_type=jnp.int32).astype(jnp.float32) \
            * s
        y = t if y is None else y + t
    return y


def _q_direct_ref(x, k: int):
    s = 2.0 ** (k - 1)
    return jnp.round(x * s) / s


def ubn_norm_ref(x: jax.Array, gamma: jax.Array, beta: jax.Array | None, *,
                 kind: str, k_mu: int, k_sigma: int, k_bn: int, k_gamma: int,
                 k_beta: int, eps: float) -> jax.Array:
    """Fused-UBN oracle: stats + normalize + the five direct quantizers.

    x: (M, N); stats over N per row ("rms"/"layer") or over M per column
    ("batch").  Bit-identical to the sim-mode core/qnorm.py composition.
    """
    axis = 0 if kind == "batch" else -1
    if kind == "rms":
        sigma = jnp.sqrt(jnp.mean(jnp.square(x), axis=axis, keepdims=True))
        xhat = x / (_q_direct_ref(sigma, k_sigma) + eps)
    else:
        mu = jnp.mean(x, axis=axis, keepdims=True)
        var = jnp.mean(jnp.square(x), axis=axis, keepdims=True) \
            - jnp.square(mu)
        sigma = jnp.sqrt(jnp.maximum(var, 0.0))
        xhat = (x - _q_direct_ref(mu, k_mu)) \
            / (_q_direct_ref(sigma, k_sigma) + eps)
    xhat = _q_direct_ref(xhat, k_bn)
    y = _q_direct_ref(gamma.reshape(1, -1), k_gamma) * xhat
    if kind != "rms":
        y = y + _q_direct_ref(beta.reshape(1, -1), k_beta)
    return y


def quantize_ref(x: jax.Array, inv_step: jax.Array, lim: float) -> jax.Array:
    """Fused shift/direct quantize payload: clip(round(x*inv_step), +-lim)."""
    return jnp.clip(jnp.round(x * inv_step), -lim, lim).astype(jnp.int8)


def cq_stochastic_ref(x: jax.Array, bits: jax.Array, inv_step: jax.Array,
                      dr: float) -> jax.Array:
    """Stochastic-rounding constant-quantize payload (paper Eq. 7).

    bits: uint32 random bits; u = low 24 bits / 2^24 in [0,1).
    Returns int16 payload on the dr grid: clip(Sr(x*inv_step), +-(dr-1)).
    """
    v = x * inv_step
    f = jnp.floor(v)
    u = (bits & jnp.uint32(0xFFFFFF)).astype(jnp.float32) * (2.0 ** -24)
    y = f + (u < (v - f)).astype(jnp.float32)
    return jnp.clip(y, -dr + 1.0, dr - 1.0).astype(jnp.int16)


def page_gather_ref(pages: jax.Array, table: jax.Array) -> jax.Array:
    """Paged KV gather: pages (P, page, ...) + table (B, NB) -> the
    contiguous per-lane view (B, NB, page, ...), all int8 (no dequantize).
    Out-of-range ids clamp (id 0 is the trash page dead lanes point at)."""
    p = pages.shape[0]
    return pages[jnp.clip(table, 0, p - 1)]


NEG_INF = -1e9   # the attention mask fill (models/layers.py uses the same)


def _pow2_ceil(m):
    """Smallest power of two >= m; 1 for m <= 0 (== core.qfuncs.pow2_ceil,
    duplicated here because kernels/ must not import core/)."""
    safe = jnp.where(m > 0, m, 1.0)
    return jnp.where(m > 0, jnp.exp2(jnp.ceil(jnp.log2(safe))), 1.0)


# Mirror of core.qfuncs._AMAX_SYNC_AXIS, set by qfuncs.amax_sync (the
# import direction is core -> kernels, so the context pushes the axis down
# here rather than kernels reading it from core).  Inside a manual-TP
# shard_map body the oracles' in-kernel GridQuantizer decompositions span
# only the local head shard; without the pmax their pow2_ceil(amax) scale
# can land one power of two away from the tp=1 value whenever the global
# amax lives on another rank's heads — a rare, input-dependent bit
# divergence (the §12 exactness contract requires every scale be global).
_AMAX_SYNC_AXIS: str | None = None


def set_amax_sync_axis(axis):
    """Set the trace-time amax pmax axis; returns the previous value."""
    global _AMAX_SYNC_AXIS
    prev = _AMAX_SYNC_AXIS
    _AMAX_SYNC_AXIS = axis
    return prev


def _grid_decompose(x: jax.Array, k: int):
    """GridQuantizer decomposition (core/qtensor.py): pow2_ceil(amax) scale
    with a 2^-24 floor, payload clip(round(x/step), +-(2^(k-1)-1)) int8.
    Returns (payload, step).  Bit-identical to _decompose + quantize_ref.
    Under amax_sync the amax is pmax'ed over the model axis — same scalar
    collective contract as core.qfuncs.amax."""
    m = jnp.max(jnp.abs(x))
    if _AMAX_SYNC_AXIS is not None:
        m = jax.lax.pmax(m, _AMAX_SYNC_AXIS)
    step = _grid_step(m, k)
    return _grid_payload(x, step, k), step


def _grid_step(amax, k: int):
    """The GridQuantizer step for an amax: pow2_ceil(amax) (floored at
    2^-24) times 2^(1-k)."""
    return jnp.maximum(_pow2_ceil(amax), 2.0 ** -24) * 2.0 ** (1 - k)


def _grid_payload(x: jax.Array, step, k: int) -> jax.Array:
    """clip(round(x / step), +-(2^(k-1)-1)) as int8 (step is pow2)."""
    lim = 2.0 ** (k - 1) - 1.0
    return jnp.clip(jnp.round(x * (jnp.float32(1.0) / step)), -lim,
                    lim).astype(jnp.int8)


def paged_attention_ref(q8: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                        table: jax.Array, q_pos: jax.Array, t_valid,
                        q_scale, k_scale, v_scale, *, sm_scale: float,
                        k_a: int = 8) -> jax.Array:
    """Fused paged decode attention oracle — operation-for-operation the
    page_gather + decode_attention composition (models/layers.py), so the
    fused op is bit-exact against the unfused path by construction.

    q8: (B, H, dh) int8 query payload (one decode token per lane);
    k_pages/v_pages: (P, page, KV, dh) int8 arenas; table: (B, NB) page
    ids (0 = trash page); q_pos: (B,) int32 per-lane positions; t_valid:
    scalar upper bound on valid positions; q/k/v_scale: pow2 payload
    scales; sm_scale: 1/sqrt(dh).

    Returns (B, H, dh) f32 — the pre-Q_A attention output.  The single
    probability amax (GridQuantizer batch-global scale) lives here as a
    scalar reduction, exactly where the unfused qeinsum puts it.
    """
    p = k_pages.shape[0]
    page, kv, dh = k_pages.shape[1:]
    b, nb = table.shape
    g = q8.shape[1] // kv
    tb = jnp.clip(table, 0, p - 1)
    k8 = k_pages[tb].reshape(b, nb * page, kv, dh)
    v8 = v_pages[tb].reshape(b, nb * page, kv, dh)
    qr = q8.reshape(b, 1, kv, g, dh)
    sc = jnp.einsum("bskgd,btkd->bskgt", qr, k8,
                    preferred_element_type=jnp.int32).astype(jnp.float32) \
        * (q_scale * k_scale)
    sc = sc * sm_scale
    t = nb * page
    kp = jnp.arange(t)
    mask = (kp[None, :] <= q_pos[:, None]) & (kp[None, :] < t_valid)
    sc = jnp.where(mask[:, None, None, None, :], sc, NEG_INF)
    m = jnp.max(sc, axis=-1, keepdims=True)
    pex = jnp.exp(sc - m)
    pn = pex / jnp.sum(pex, axis=-1, keepdims=True)
    s_ = 2.0 ** (k_a - 1)
    pg = jnp.round(pn * s_) / s_                       # qprobs (Q_A grid)
    p8, step = _grid_decompose(pg, k_a)                # ONE batch-global amax
    out = jnp.einsum("bskgt,btkd->bskgd", p8, v8,
                     preferred_element_type=jnp.int32).astype(jnp.float32) \
        * (step * v_scale)
    return out.reshape(b, kv * g, dh)


def flash_attention_ref(q8: jax.Array, k8: jax.Array, v8: jax.Array,
                        q_pos: jax.Array, k_pos: jax.Array,
                        k_valid: jax.Array, q_scale, k_scale, v_scale, *,
                        causal: bool, sm_scale: float, q_chunk: int,
                        kv_chunk: int, k_a: int = 8) -> jax.Array:
    """Tiled online-softmax attention oracle on int8 payload operands.

    Chunk-for-chunk the pure-JAX chunked_attention composition
    (models/layers.py): scores and p·v run as integer dots with per-chunk
    GridQuantizer decompositions (amax over the full (B, chunk, heads)
    block — including the saturate-at-amax-pow2 corner), probabilities
    quantize UNNORMALIZED onto the Q_A grid per kv step, and the online
    rescale (m/l/alpha) runs in f32.  Bit-identical to the unfused path.

    q8: (B, S, H, dh) int8; k8/v8: (B, T, KV, dh) int8 — all pre-padded to
    chunk multiples (payload zeros); q_pos: (S,), k_pos: (T,) int32;
    k_valid: (T,) mask of real (non-padded) kv slots; scales: pow2 payload
    scales.  Returns (B, S, H, dh) f32 (padded rows included; the caller
    slices and applies Q_A).  Control flow (lax.scan over kv chunks,
    lax.map over q blocks) is structured exactly like the unfused body so
    the two compile to the same program shape.
    """
    b, s, h, dh = q8.shape
    t, kv = k8.shape[1], k8.shape[2]
    g = h // kv
    nq, nk = s // q_chunk, t // kv_chunk
    qf = (q8.astype(jnp.float32) * q_scale).reshape(b, s, kv, g, dh)
    kf = k8.astype(jnp.float32) * k_scale
    vf = v8.astype(jnp.float32) * v_scale
    s_ = 2.0 ** (k_a - 1)
    kc = kf.reshape(b, nk, kv_chunk, kv, dh).transpose(1, 0, 2, 3, 4)
    vc = vf.reshape(b, nk, kv_chunk, kv, dh).transpose(1, 0, 2, 3, 4)
    kpc = k_pos.reshape(nk, kv_chunk)
    kvc = (k_valid != 0).reshape(nk, kv_chunk)

    def q_block(qi, qp):
        qi8, q_step = _grid_decompose(qi, k_a)

        def kv_step(carry, inp):
            m, l, o = carry
            ki, vi, kp, kval = inp
            ki8, k_step = _grid_decompose(ki, k_a)
            sc = jnp.einsum("bskgd,btkd->bskgt", qi8, ki8,
                            preferred_element_type=jnp.int32) \
                .astype(jnp.float32) * (q_step * k_step)
            sc = sc * sm_scale
            mask = kval[None, :] if not causal else (
                (qp[:, None] >= kp[None, :]) & kval[None, :])
            sc = jnp.where(mask[None, :, None, None, :], sc, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(sc, axis=-1))
            p = jnp.exp(sc - m_new[..., None])
            p = jnp.round(p * s_) / s_             # qprobs, unnormalized
            pi8, p_step = _grid_decompose(p, k_a)
            vi8, v_step = _grid_decompose(vi, k_a)
            pv = jnp.einsum("bskgt,btkd->bskgd", pi8, vi8,
                            preferred_element_type=jnp.int32) \
                .astype(jnp.float32) * (p_step * v_step)
            alpha = jnp.exp(m - m_new)
            l = l * alpha + jnp.sum(p, axis=-1)
            o = o * alpha[..., None] + pv
            return (m_new, l, o), None

        m0 = jnp.full(qi.shape[:-1], NEG_INF, jnp.float32)
        l0 = jnp.zeros(qi.shape[:-1], jnp.float32)
        o0 = jnp.zeros(qi.shape, jnp.float32)
        (m, l, o), _ = jax.lax.scan(kv_step, (m0, l0, o0),
                                    (kc, vc, kpc, kvc))
        return o / jnp.maximum(l, 1e-9)[..., None]

    qb = qf.reshape(b, nq, q_chunk, kv, g, dh).transpose(1, 0, 2, 3, 4, 5)
    qpb = q_pos.reshape(nq, q_chunk)
    out = jax.lax.map(lambda args: q_block(*args), (qb, qpb))
    return out.transpose(1, 0, 2, 3, 4, 5).reshape(b, s, h, dh)


def selective_scan_ref(a: jax.Array, b: jax.Array, c: jax.Array) -> jax.Array:
    """h_t = a_t * h_{t-1} + b_t (h_0 = 0);  y_t = sum_n c_t[n] * h_t[:, n].

    a, b: (B, S, D, N); c: (B, S, N) -> y: (B, S, D).
    """
    def scan_one(a1, b1, c1):
        def step(h, inp):
            ai, bi, ci = inp
            h = ai * h + bi
            return h, jnp.sum(h * ci[None, :], axis=-1)
        h0 = jnp.zeros(a1.shape[1:], jnp.float32)
        _, y = jax.lax.scan(step, h0, (a1, b1, c1))
        return y
    return jax.vmap(scan_one)(a, b, c)
