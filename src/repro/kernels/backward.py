"""Pallas TPU kernels: fused-prologue backward matmuls (paper Alg. 2).

WAGEUBN's backward runs both gradient dots on integer operands after the
incoming error is quantized with Q_E2 (paper e3 = Q_E2(e2)).  These kernels
fuse that quantization into the matmul PROLOGUE: each fp32 error block is
quantized to its integer payload plane(s) in VMEM registers and fed straight
to the MXU — the int8/int16 error tensor is never materialized in HBM and no
standalone quantize pass runs between Q_E2 and the matmuls.

  bwd_dgrad — da = dequant( Qe(g) ·_int b8ᵀ ): einsum('mn,kn->mk'), the
              input-error dot e4 = W^T e3 of Alg. 2 (b8 holds W's payload).
  bwd_wgrad — db = dequant( a8ᵀ ·_int Qe(g) ): einsum('mk,mn->kn'), the
              weight-gradient dot g_W = e3 x0^T of Alg. 2 (a8 holds x0).

Prologue modes (static):
  "affine" — payload = clip(round(g * inv), ±lim), one plane (SQ / grid /
             direct formats; int8 for k<=8, int16 above).
  "flag"   — the two-plane flag format (paper Eq. 17): hi multiples of Sc,
             lo multiples of Sc*2^(1-k), disjoint support, both int8.

Scalars arrive as one (1, 3) f32 plane [inv, s1, s2]: `inv` is the exact
pow2 reciprocal of the payload step, `s1`/`s2` the per-plane epilogue output
scales (plane_step * other_operand_scale — pow2 products, exact in fp32).
The quantized g block is recomputed per output tile (VPU work overlapped
with the MXU) instead of being staged through HBM.

Bit-exact vs ref.dgrad_ref / ref.wgrad_ref, which themselves reproduce the
unfused `Quantizer.quantize` + integer-einsum path (tests/test_kernels.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _payload_dtype(k: int):
    return jnp.int8 if k <= 8 else jnp.int16


def _quantize_block(g, inv, *, mode: str, k: int):
    """fp32 block -> integer payload plane(s), entirely in registers."""
    lim = 2.0 ** (k - 1) - 1.0
    dt = _payload_dtype(k)
    if mode == "affine":
        q = jnp.clip(jnp.round(g * inv), -lim, lim).astype(dt)
        return (q,)
    assert mode == "flag", mode
    n = g * inv                                  # inv = 1/Sc (pow2, exact)
    nlo = jnp.round(n * 2.0 ** (k - 1))
    # |nlo| >= 2^(k-1) collapses to the hi regime (same value there)
    isbig = (jnp.abs(n) >= 1.0) | (jnp.abs(nlo) >= 2.0 ** (k - 1))
    hi = jnp.where(isbig, jnp.clip(jnp.round(n), -lim, lim), 0.0)
    lo = jnp.where(isbig, 0.0, jnp.clip(nlo, -lim, lim))
    return (hi.astype(dt), lo.astype(dt))


def _bwd_kernel(g_ref, b_ref, s_ref, o_ref, acc1, acc2, *, mode, k, dgrad):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc1[...] = jnp.zeros_like(acc1)
        if acc2 is not None:
            acc2[...] = jnp.zeros_like(acc2)

    planes = _quantize_block(g_ref[...], s_ref[0, 0], mode=mode, k=k)
    b = b_ref[...]
    for q, acc in zip(planes, (acc1, acc2)):
        if dgrad:        # (bm, bn) x (bk, bn) -> (bm, bk), contract on n
            acc[...] += lax.dot_general(q, b, (((1,), (1,)), ((), ())),
                                        preferred_element_type=jnp.int32)
        else:            # (bm, bk) x (bm, bn) -> (bk, bn), contract on m
            acc[...] += lax.dot_general(b, q, (((0,), (0,)), ((), ())),
                                        preferred_element_type=jnp.int32)

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _flush():
        o = acc1[...].astype(jnp.float32) * s_ref[0, 1]
        if acc2 is not None:
            o = o + acc2[...].astype(jnp.float32) * s_ref[0, 2]
        o_ref[...] = o


def _bwd_call(g, other, scal, out_shape, specs, out_spec, grid, *,
              mode, k, dgrad, name, interpret):
    two = mode == "flag"
    bo = out_spec.block_shape
    scratch = [pltpu.VMEM(bo, jnp.int32) for _ in range(2 if two else 1)]

    def kernel(g_ref, b_ref, s_ref, o_ref, acc1, acc2=None):
        _bwd_kernel(g_ref, b_ref, s_ref, o_ref, acc1, acc2,
                    mode=mode, k=k, dgrad=dgrad)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=specs,
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct(out_shape, jnp.float32),
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name=name,
        interpret=interpret,
    )(g, other, scal.reshape(1, 3))


@functools.partial(jax.jit, static_argnames=("mode", "k", "bm", "bk", "bn",
                                             "interpret"))
def bwd_dgrad(g: jax.Array, b8: jax.Array, scal: jax.Array, *, mode: str,
              k: int = 8, bm: int = 128, bk: int = 128, bn: int = 128,
              interpret: bool = True) -> jax.Array:
    """da (M, K) = sum_planes [Qe(g) (M, N) ·_int b8 (K, N)ᵀ] * s_plane.

    g: fp32 error; b8: int8 payload of the other forward operand (W);
    scal: (3,) f32 [inv, s1, s2].  Error quantization (mode, k) happens in
    the kernel prologue; no integer error tensor ever reaches HBM.
    """
    m, n = g.shape
    kk, n2 = b8.shape
    assert n == n2
    bm, bk, bn = min(bm, m), min(bk, kk), min(bn, n)
    pm, pk, pn = (-m) % bm, (-kk) % bk, (-n) % bn
    if pm or pn:
        g = jnp.pad(g, ((0, pm), (0, pn)))
    if pk or pn:
        b8 = jnp.pad(b8, ((0, pk), (0, pn)))
    grid = ((m + pm) // bm, (kk + pk) // bk, (n + pn) // bn)
    specs = [pl.BlockSpec((bm, bn), lambda i, j, l: (i, l)),
             pl.BlockSpec((bk, bn), lambda i, j, l: (j, l)),
             pl.BlockSpec((1, 3), lambda i, j, l: (0, 0))]
    out_spec = pl.BlockSpec((bm, bk), lambda i, j, l: (i, j))
    out = _bwd_call(g, b8, scal, (m + pm, kk + pk), specs, out_spec, grid,
                    mode=mode, k=k, dgrad=True, name="bwd_dgrad",
                    interpret=interpret)
    return out[:m, :kk]


@functools.partial(jax.jit, static_argnames=("mode", "k", "bm", "bk", "bn",
                                             "interpret"))
def bwd_wgrad(a8: jax.Array, g: jax.Array, scal: jax.Array, *, mode: str,
              k: int = 8, bm: int = 128, bk: int = 128, bn: int = 128,
              interpret: bool = True) -> jax.Array:
    """db (K, N) = sum_planes [a8 (M, K)ᵀ ·_int Qe(g) (M, N)] * s_plane.

    a8: int8 payload of the saved forward activation x0; g: fp32 error;
    scal: (3,) f32 [inv, s1, s2].  Same fused prologue as bwd_dgrad.
    """
    m, kk = a8.shape
    m2, n = g.shape
    assert m == m2
    bm, bk, bn = min(bm, m), min(bk, kk), min(bn, n)
    pm, pk, pn = (-m) % bm, (-kk) % bk, (-n) % bn
    if pm or pn:
        g = jnp.pad(g, ((0, pm), (0, pn)))
    if pm or pk:
        a8 = jnp.pad(a8, ((0, pm), (0, pk)))
    grid = ((kk + pk) // bk, (n + pn) // bn, (m + pm) // bm)
    specs = [pl.BlockSpec((bm, bn), lambda i, j, l: (l, j)),
             pl.BlockSpec((bm, bk), lambda i, j, l: (l, i)),
             pl.BlockSpec((1, 3), lambda i, j, l: (0, 0))]
    out_spec = pl.BlockSpec((bk, bn), lambda i, j, l: (i, j))
    out = _bwd_call(g, a8, scal, (kk + pk, n + pn), specs, out_spec, grid,
                    mode=mode, k=k, dgrad=False, name="bwd_wgrad",
                    interpret=interpret)
    return out[:kk, :n]
