"""Pallas TPU kernel: Mamba1 selective scan with VMEM-resident state.

The recurrence h_t = a_t * h_{t-1} + b_t is sequential in t, so the grid is
(batch, channel-blocks, seq-blocks) with the SEQ dimension innermost and
"arbitrary" (sequential); the (bd, N) state lives in VMEM scratch and
persists across seq-grid steps — HBM traffic is exactly one read of a/b/c
and one write of y (the jnp fallback materializes (B,S,D,N) intermediates).

This is the TPU-native answer to the paper-adjacent CUDA selective-scan
kernel: no warp shuffles — VMEM residency + sequential grid instead.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssm_kernel(a_ref, b_ref, c_ref, o_ref, h_ref, *, bs):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    def step(t, h):
        h = a_ref[0, t] * h + b_ref[0, t]                  # (bd, N)
        o_ref[0, t, :] = jnp.sum(h * c_ref[0, t][None, :], axis=-1)
        return h

    h_ref[...] = lax.fori_loop(0, bs, step, h_ref[...])


@functools.partial(jax.jit, static_argnames=("bd", "bs", "interpret"))
def selective_scan(a: jax.Array, b: jax.Array, c: jax.Array, *,
                   bd: int = 128, bs: int = 32,
                   interpret: bool = True) -> jax.Array:
    """a, b: (B, S, D, N) f32; c: (B, S, N) f32 -> y: (B, S, D) f32.

    The (bs, bd, N) blocks pad N to 128 lanes on a TPU, so bd * bs stays at
    4096: two double-buffered inputs then fit the 16 MiB scoped VMEM at
    falcon-mamba-7b widths (v5e compile).  bd is the output block's lane
    dim (a multiple of 128 or all of D), bs a multiple of 8 or all of S.
    """
    bsz, s, d, n = a.shape
    bd, bs = min(bd, d), min(bs, s)
    pd, ps = (-d) % bd, (-s) % bs
    if pd or ps:
        a = jnp.pad(a, ((0, 0), (0, ps), (0, pd), (0, 0)),
                    constant_values=1.0)
        b = jnp.pad(b, ((0, 0), (0, ps), (0, pd), (0, 0)))
        c = jnp.pad(c, ((0, 0), (0, ps), (0, 0)))
    dd, ss = d + pd, s + ps

    grid = (bsz, dd // bd, ss // bs)
    out = pl.pallas_call(
        functools.partial(_ssm_kernel, bs=bs),
        grid=grid,
        in_specs=[pl.BlockSpec((1, bs, bd, n), lambda i, j, k: (i, k, j, 0)),
                  pl.BlockSpec((1, bs, bd, n), lambda i, j, k: (i, k, j, 0)),
                  pl.BlockSpec((1, bs, n), lambda i, j, k: (i, k, 0))],
        out_specs=pl.BlockSpec((1, bs, bd), lambda i, j, k: (i, k, j)),
        out_shape=jax.ShapeDtypeStruct((bsz, ss, dd), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bd, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="selective_scan",
        interpret=interpret,
    )(a, b, c)
    return out[:, :s, :d]
