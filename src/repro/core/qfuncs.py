"""WAGEUBN quantization functions (paper §III-C) + fixed-point helpers.

All "grid" tensors are fp32 arrays whose values lie *exactly* on a fixed-point
grid: x = n * step with step a power of two and |n| < 2^(k-1).  Every paper
width k <= 24 fits exactly in fp32's 24-bit mantissa, so fp32 VPU arithmetic
on grid values is bit-identical to integer arithmetic (see DESIGN.md §3).

Three quantizers (paper Eq. 6/7/8/17):
  q_direct  — round onto the 2^-(k-1) grid                       (W, A, BN)
  cq        — stochastic-rounded, range-normalized, constant-scaled (G)
  sq        — shift quantization with layer-wise pow2 scale R(x)    (E)
  flag_qe2  — 8-bit + flag-bit format, two pow2 regimes             (e3)
"""
from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp

Array = jax.Array

# --------------------------------------------------------------------------
# basic fixed-point helpers
# --------------------------------------------------------------------------


def d(k: int) -> float:
    """Minimum interval of a k-bit fixed-point grid (paper Eq. 8)."""
    return 2.0 ** (1 - k)


# Trace-time amax synchronization for manual tensor parallelism: inside a
# shard_map body every amax-derived scale must be GLOBAL (the tp=1 value),
# or per-rank quantization grids would diverge and sharded outputs would
# stop being exact slices of the single-device computation.  The sync is a
# scalar pmax — a float collective, but a SCALAR one, which the sharded
# wire contract explicitly permits (DESIGN.md §9/§12).
_AMAX_SYNC_AXIS: str | None = None


@contextlib.contextmanager
def amax_sync(axis: str | None):
    """Within this context, amax() pmaxes its result over `axis`.

    Applied at TRACE time: wrap the shard_map body so every quantizer scale
    computed inside agrees across model ranks.  pmax over ranks that hold
    identical replicated values (or over a size-1 axis at tp=1) is the
    identity, so the contract costs nothing when nothing is sharded.
    """
    global _AMAX_SYNC_AXIS
    from repro.kernels import ref as _kref   # core -> kernels only
    prev = _AMAX_SYNC_AXIS
    _AMAX_SYNC_AXIS = axis
    # the fused oracles run their own in-body GridQuantizer decompositions
    # (kernels/ref.py); their amax must obey the same global-scale contract
    prev_k = _kref.set_amax_sync_axis(axis)
    try:
        yield
    finally:
        _AMAX_SYNC_AXIS = prev
        _kref.set_amax_sync_axis(prev_k)


def amax(x: Array) -> Array:
    """max|x|, the one reduction in front of every layer-wise quantizer
    (pmaxed over the `amax_sync` axis when one is set)."""
    with jax.named_scope("amax"):
        m = jnp.max(jnp.abs(x))
        if _AMAX_SYNC_AXIS is not None:
            m = jax.lax.pmax(m, _AMAX_SYNC_AXIS)
        return m


def pow2_round(m: Array) -> Array:
    """R(x) = 2^round(log2 m) for m = max|x| (paper Eq. 7); R(0) := 1."""
    safe = jnp.where(m > 0, m, 1.0)
    return jnp.where(m > 0, jnp.exp2(jnp.round(jnp.log2(safe))), 1.0)


def pow2_ceil(m: Array) -> Array:
    """Smallest power of two >= m; 1 for m <= 0."""
    safe = jnp.where(m > 0, m, 1.0)
    return jnp.where(m > 0, jnp.exp2(jnp.ceil(jnp.log2(safe))), 1.0)


def q_direct(x: Array, k: int) -> Array:
    """Direct quantization Q(x,k) = round(x*2^(k-1)) / 2^(k-1)  (Eq. 6)."""
    s = 2.0 ** (k - 1)
    return jnp.round(x * s) / s


def q_clip(x: Array, k: int) -> Array:
    """Direct quantization + saturation to (-1, 1): used for W (Eq. 10)."""
    lim = 1.0 - d(k)
    return jnp.clip(q_direct(x, k), -lim, lim)


def sq(x: Array, k: int) -> Array:
    """Shift quantization SQ(x,k) = R * clip(Q(x/R, k), +-(1-d))  (Eq. 8)."""
    r = pow2_round(amax(x))
    lim = 1.0 - d(k)
    return r * jnp.clip(q_direct(x / r, k), -lim, lim)


def q_scaled(x: Array, k: int) -> Array:
    """Scaled direct quantization for activations in the int8-native carrier.

    Identical to the paper's Q_A (Eq. 14) whenever max|x| < 1; for larger
    dynamic range a power-of-two amax factor extends coverage (this is
    exactly WAGE's layer-wise scaling, see DESIGN.md §3).  Guarantees the
    result is s * n * 2^-(k-1) with |n| <= 2^(k-1)-1 (int8-packable @ k=8).
    """
    s = jnp.maximum(pow2_ceil(amax(x)), 1.0)
    lim = 1.0 - d(k)
    return s * jnp.clip(q_direct(x / s, k), -lim, lim)


def stochastic_round(x: Array, key: Array) -> Array:
    """Sr(x) (Eq. 7): round to floor/ceil with probability by proximity."""
    f = jnp.floor(x)
    p = x - f
    u = jax.random.uniform(key, x.shape, dtype=x.dtype)
    return f + (u < p).astype(x.dtype)


def cq(x: Array, key: Array | None, dr_bits: int, k_gc: int,
       stochastic: bool = True) -> Array:
    """Constant quantization CQ (Eq. 7) for weight gradients G.

    dr = 2^(dr_bits-1) shrinks during training (learning-rate-like schedule);
    the output lives on the 2^-(k_gc-1) grid with range +-(dr-1)*2^-(k_gc-1).
    """
    r = pow2_round(amax(x))
    n = x / r
    dr = float(2 ** (dr_bits - 1))
    y = dr * n
    if stochastic:
        assert key is not None, "stochastic CQ needs a PRNG key"
        y = stochastic_round(y, key)
    else:
        y = jnp.round(y)
    y = jnp.clip(y, -dr + 1.0, dr - 1.0)
    return y / 2.0 ** (k_gc - 1)


def flag_qe2(x: Array, k: int = 8) -> Array:
    """Flag-bit error quantization (Eq. 17 / Fig. 4).

    Sc = R(x)/2^(k-1).  Two regimes sharing an int8 mantissa:
      |x| >= Sc : multiples of Sc       (flag=1)   n in +-(2^(k-1)-1)
      |x| <  Sc : multiples of Sc/2^(k-1) (flag=0)
    Note: Eq. 17 writes clip bounds +-(2^k - 1) but Fig. 4's bit layout
    (sign + 7 data bits) implies +-(2^(k-1)-1); we follow Fig. 4 so the
    mantissa is a true int8 (the MXU datapath the paper argues for).
    """
    r = pow2_round(amax(x))
    sc = r / 2.0 ** (k - 1)
    n = x / sc
    lim = 2.0 ** (k - 1) - 1.0
    big = sc * jnp.clip(jnp.round(n), -lim, lim)
    small = sc * q_direct(n, k)  # multiples of sc * 2^-(k-1)
    return jnp.where(jnp.abs(n) >= 1.0, big, small)


def quant_error(x: Array, kind: str, k_e: int) -> Array:
    """DEPRECATED shim: error-quantizer dispatch now lives in the quantizer
    registry (qtensor.py); legacy string kinds resolve via ALIASES."""
    from .qtensor import resolve_quantizer
    return resolve_quantizer(kind, k_e)(x)


# --------------------------------------------------------------------------
# straight-through estimator (paper Eq. 1)
# --------------------------------------------------------------------------


def ste(fn, x: Array) -> Array:
    """y = fn(x) in the forward pass; identity cotangent in the backward."""

    @jax.custom_vjp
    def f(t):
        return fn(t)

    f.defvjp(lambda t: (fn(t), None), lambda _, g: (g,))
    return f(x)


# --------------------------------------------------------------------------
# int payload decomposition (native mode)
# --------------------------------------------------------------------------


def dec_int8(x: Array, k: int = 8):
    """DEPRECATED shim for the "grid" quantizer: decompose a grid tensor
    into (int8 data, fp32 scalar scale).  value = data * scale, scale a
    power of two.  Exact (lossless) whenever x came from q_scaled/q_clip/sq
    at width <= k; otherwise it quantizes."""
    from .qtensor import get_quantizer
    qt = get_quantizer("grid", k).quantize(x)
    return qt.data, qt.scale


def dec_int8_fixed(x: Array, k: int = 8):
    """DEPRECATED shim for the "clip" quantizer's payload: int8 decomposition
    with the FIXED step 2^(1-k) — exact for tensors already saturated to
    (-1, 1) by q_clip (i.e. Q_W weights).  No amax pass, no scalar
    collective; the int8 copy is what FSDP gathers."""
    from .qtensor import get_quantizer
    qt = get_quantizer("clip", k).quantize(x)
    return qt.data, qt.scale


def dec_int16(x: Array, k: int = 16):
    """DEPRECATED shim: dec_int8 for 16-bit payloads (e.g. sq16 errors)."""
    from .qtensor import get_quantizer
    qt = get_quantizer("grid", k).quantize(x)
    return qt.data, qt.scale


def dec_error(x: Array, kind: str, k_e: int):
    """DEPRECATED shim: decompose an error tensor into integer planes.

    Registry-backed (see qtensor.Quantizer.planes).  Returns a list of
    (data, scale) planes:
      sq8   -> [(int8, R*2^-7)]
      sq16  -> [(int16, R*2^-15)]
      flag8 -> [(int8 hi, Sc), (int8 lo, Sc*2^-7)]  (disjoint support; this is
               the TPU realization of the paper's 9-bit flag format: storage
               and both backward dots stay int8)
    """
    from .qtensor import resolve_quantizer
    q = resolve_quantizer(kind, k_e)
    return list(q.planes(q.quantize(x)))
