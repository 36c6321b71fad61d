"""Pallas TPU kernels: fused quantization (paper Eq. 6/7/8 inner loops).

quantize_fused    — one pass over x: scale, round, saturate, emit the int8
                    payload (the Q / SQ hot loop after the amax prepass).
                    Any rank, on a (rows, minor dim) view.
cq_stochastic     — the CQ stochastic-rounding loop (Eq. 7): floor + coin
                    flip from uniform bits, saturate to the dr range, int16
                    payload.  Random bits arrive as a uint32 input plane
                    (jax.random.bits outside -> deterministic and testable;
                    on real TPU swap in pltpu.prng_random_bits and drop the
                    input — kept as a flag-gated path).

Both are elementwise over 2-D VMEM blocks.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# VMEM bytes of one quantize block, input and int8 output together, counted
# on their padded tiles.  The pipeline double-buffers both, so a kernel
# holds 8 MiB: under v5e's 16 MiB default scoped VMEM.
BLOCK_BYTES = 4 * 2 ** 20


def rows_view(shape) -> tuple[int, int]:
    """(rows, C) view of an array: every dim but the minor one merged into
    rows, the form in which norms hand activations on ((B·H·W, C) for NHWC,
    (kh·kw·Cin, Cout) for HWIO).  Under TPU's (8, 128) tiling it is a
    bitcast of any array whose second-minor dim is a multiple of 8, so it
    moves no bytes; 1-D and 0-D are (1, N)."""
    c = shape[-1] if shape else 1
    return math.prod(shape[:-1]), c


def _pad(n: int, m: int) -> int:
    return -(-n // m) * m


def quantize_blocks(m: int, c: int, itemsize: int,
                    budget: int = BLOCK_BYTES) -> tuple[int, int]:
    """(br, bc) block of an (M, C) view whose input and int8 output, padded
    to their (sublane, 128) tiles, fit `budget` VMEM bytes.  C stays whole
    where 32 rows of it fit, else tiles in multiples of 128; rows take as
    many multiples of 32 (the int8 tile's rows) as fit, or all M."""
    sub = 32 // itemsize                    # input's sublane tile: 8 for f32

    def cost(rows, cols):
        return (_pad(rows, sub) * itemsize + _pad(rows, 32)) * _pad(cols, 128)

    bc = c
    if cost(32, c) > budget:
        bc = max(128, budget // cost(32, 128) * 128)
    br = max(32, budget // cost(32, bc) * 32)
    return (m if br >= m else br), bc


def _quant_kernel(x_ref, s_ref, o_ref, *, lim):
    inv = s_ref[0, 0]
    v = jnp.round(x_ref[...] * inv)
    o_ref[...] = jnp.clip(v, -lim, lim).astype(jnp.int8)


@functools.partial(jax.jit,
                   static_argnames=("lim", "block_bytes", "interpret"))
def quantize_fused(x: jax.Array, inv_step: jax.Array, *, lim: float = 127.0,
                   block_bytes: int = BLOCK_BYTES,
                   interpret: bool = True) -> jax.Array:
    """x: f32 of any shape; inv_step: scalar f32 -> int8 payload, x's shape.

    Runs on the (M, C) view (`rows_view`) in (br, bc) blocks
    (`quantize_blocks`); a grid that does not divide the view leaves the
    last block ragged, and Pallas masks it, so no padded copy exists."""
    m, c = rows_view(x.shape)
    br, bc = quantize_blocks(m, c, x.dtype.itemsize, block_bytes)
    block = pl.BlockSpec((br, bc), lambda i, j: (i, j))
    x2, out_shape = x.reshape(m, c), jax.ShapeDtypeStruct((m, c), jnp.int8)
    if not interpret:
        # operand and payload stay in HBM: the kernel moves its own bytes,
        # where XLA would otherwise stage them in VMEM through async copies
        # that its trace events leave out
        x2 = pltpu.with_memory_space_constraint(x2, pltpu.HBM)
        out_shape = pltpu.HBM((m, c), jnp.int8)
    out = pl.pallas_call(
        functools.partial(_quant_kernel, lim=lim),
        grid=(pl.cdiv(m, br), pl.cdiv(c, bc)),
        in_specs=[block, pl.BlockSpec((1, 1), lambda i, j: (0, 0))],
        out_specs=block,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        name="quantize_fused",
        interpret=interpret,
    )(x2, inv_step.reshape(1, 1))
    return out.reshape(x.shape)


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
