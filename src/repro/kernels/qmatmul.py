"""Pallas TPU kernel: blocked int8 x int8 -> int32 matmul (the WAGEUBN MAC).

MXU-native tiling: (bm, bk) x (bk, bn) int8 blocks feed the systolic array;
the int32 accumulator lives in VMEM scratch and persists across the K grid
dimension (sequential innermost).  Block shapes default to 128-aligned —
the MXU operates on 128x128 tiles; int8 packs 2 values/lane so bk=256 keeps
the lanes full on real hardware.

`qmatmul` optionally fuses a REQUANTIZE EPILOGUE (DESIGN.md §8): at the
final K step the int32 accumulator is rescaled by a power-of-two scalar,
rounded, clipped, and emitted as an int8 payload directly — the consumer
gets a QTensor payload on a known grid without an fp32 carrier ever being
materialized in HBM or a separate quantize pass running over it.

Validated in interpret mode against ref.qmatmul_ref / qmatmul_requant_ref
(this container is CPU-only; TPU is the compilation target).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _qmm_kernel(a_ref, b_ref, o_ref, acc_ref):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(a_ref[...], b_ref[...],
                            preferred_element_type=jnp.int32)

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _flush():
        o_ref[...] = acc_ref[...]


def _qmm_requant_kernel(a_ref, b_ref, s_ref, o_ref, acc_ref, *, lim):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(a_ref[...], b_ref[...],
                            preferred_element_type=jnp.int32)

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _flush():
        # fused epilogue: int32 accumulate -> pow2 rescale -> round -> clip,
        # emitting the int8 payload without an fp32 carrier round trip
        v = jnp.round(acc_ref[...].astype(jnp.float32) * s_ref[0, 0])
        o_ref[...] = jnp.clip(v, -lim, lim).astype(jnp.int8)


@functools.partial(jax.jit, static_argnames=("lim", "bm", "bn", "bk",
                                             "interpret"))
def qmatmul(a8: jax.Array, b8: jax.Array, requant_inv: jax.Array | None = None,
            *, lim: float = 127.0, bm: int = 128, bn: int = 128,
            bk: int = 256, interpret: bool = True) -> jax.Array:
    """Blocked integer matmul, optionally with a fused requantize epilogue.

    Args:
      a8: (M, K) int8 payload.
      b8: (K, N) int8 payload.
      requant_inv: optional scalar f32 — the combined pow2 rescale
        `a_scale * b_scale / out_step`.  When given, the epilogue emits
        `clip(round(acc * requant_inv), +-lim)` as int8.
      lim: epilogue clip bound (only used with requant_inv).

    Returns:
      (M, N) int32 accumulator, or (M, N) int8 payload when requant_inv
      is given.
    """
    m, k = a8.shape
    k2, n = b8.shape
    assert k == k2
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, k)
    pm, pn, pk = (-m) % bm, (-n) % bn, (-k) % bk
    if pm or pk:
        a8 = jnp.pad(a8, ((0, pm), (0, pk)))
    if pk or pn:
        b8 = jnp.pad(b8, ((0, pk), (0, pn)))
    mm, nn, kk = m + pm, n + pn, k + pk

    grid = (mm // bm, nn // bn, kk // bk)
    in_specs = [pl.BlockSpec((bm, bk), lambda i, j, l: (i, l)),
                pl.BlockSpec((bk, bn), lambda i, j, l: (l, j))]
    if requant_inv is None:
        kernel, out_dtype, operands = _qmm_kernel, jnp.int32, (a8, b8)
    else:
        kernel = functools.partial(_qmm_requant_kernel, lim=lim)
        out_dtype = jnp.int8
        in_specs.append(pl.BlockSpec((1, 1), lambda i, j, l: (0, 0)))
        operands = (a8, b8, jnp.asarray(requant_inv,
                                        jnp.float32).reshape(1, 1))
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, l: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mm, nn), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="qmatmul",
        interpret=interpret,
    )(*operands)
    return out[:m, :n]
