"""Pallas TPU kernels: fused quantization (paper Eq. 6/7/8 inner loops).

quantize_fused    — one pass over x: scale, round, saturate, emit the int8
                    payload (the Q / SQ hot loop after the amax prepass).
cq_stochastic     — the CQ stochastic-rounding loop (Eq. 7): floor + coin
                    flip from uniform bits, saturate to the dr range, int16
                    payload.  Random bits arrive as a uint32 input plane
                    (jax.random.bits outside -> deterministic and testable;
                    on real TPU swap in pltpu.prng_random_bits and drop the
                    input — kept as a flag-gated path).

Both are elementwise over 2D blocks: (bm, bn) VMEM tiles, 8x128-aligned.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl


def _quant_kernel(x_ref, s_ref, o_ref, *, lim):
    inv = s_ref[0, 0]
    v = jnp.round(x_ref[...] * inv)
    o_ref[...] = jnp.clip(v, -lim, lim).astype(jnp.int8)


@functools.partial(jax.jit, static_argnames=("lim", "bm", "bn", "interpret"))
def quantize_fused(x: jax.Array, inv_step: jax.Array, *, lim: float = 127.0,
                   bm: int = 256, bn: int = 256,
                   interpret: bool = True) -> jax.Array:
    """x: (M, N) f32; inv_step: scalar f32 -> int8 payload (M, N)."""
    m, n = x.shape
    bm, bn = min(bm, m), min(bn, n)
    pm, pn = (-m) % bm, (-n) % bn
    if pm or pn:
        x = jnp.pad(x, ((0, pm), (0, pn)))
    grid = ((m + pm) // bm, (n + pn) // bn)
    out = pl.pallas_call(
        functools.partial(_quant_kernel, lim=lim),
        grid=grid,
        in_specs=[pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
                  pl.BlockSpec((1, 1), lambda i, j: (0, 0))],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m + pm, n + pn), jnp.int8),
        name="quantize_fused",
        interpret=interpret,
    )(x, inv_step.reshape(1, 1))
    return out[:m, :n]


def _cq_kernel(x_ref, bits_ref, s_ref, o_ref, *, dr):
    inv = s_ref[0, 0]
    v = x_ref[...] * inv
    f = jnp.floor(v)
    # Mosaic has no uint32 -> f32 cast; the masked 24-bit value is
    # non-negative, so the int32 view converts to the same float
    u24 = lax.bitcast_convert_type(bits_ref[...] & jnp.uint32(0xFFFFFF),
                                   jnp.int32)
    u = u24.astype(jnp.float32) * (2.0 ** -24)
    y = f + (u < (v - f)).astype(jnp.float32)
    o_ref[...] = jnp.clip(y, -dr + 1.0, dr - 1.0).astype(jnp.int16)


@functools.partial(jax.jit, static_argnames=("dr", "bm", "bn", "interpret"))
def cq_stochastic(x: jax.Array, bits: jax.Array, inv_step: jax.Array, *,
                  dr: float = 128.0, bm: int = 256, bn: int = 256,
                  interpret: bool = True) -> jax.Array:
    """Stochastic CQ payload (Eq. 7).  x,(bits): (M, N) -> int16 (M, N)."""
    m, n = x.shape
    bm, bn = min(bm, m), min(bn, n)
    pm, pn = (-m) % bm, (-n) % bn
    if pm or pn:
        x = jnp.pad(x, ((0, pm), (0, pn)))
        bits = jnp.pad(bits, ((0, pm), (0, pn)))
    grid = ((m + pm) // bm, (n + pn) // bn)
    out = pl.pallas_call(
        functools.partial(_cq_kernel, dr=dr),
        grid=grid,
        in_specs=[pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
                  pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
                  pl.BlockSpec((1, 1), lambda i, j: (0, 0))],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m + pm, n + pn), jnp.int16),
        name="cq_stochastic",
        interpret=interpret,
    )(x, bits, inv_step.reshape(1, 1))
    return out[:m, :n]
