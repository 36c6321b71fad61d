"""Pallas TPU kernel: fused UBN — statistics, normalize, quantize, one pass.

The paper's quantized norm (Eq. 11-13) runs five direct quantizers around
the normalization arithmetic: Q(mu, k_mu), Q(sigma, k_sigma), Q(xhat, k_BN),
Q(gamma, k_gamma), Q(beta, k_beta).  As separate XLA passes each one
re-reads and re-writes the full activation between stages; here the whole
chain is ONE kernel pass per tile: statistics reduce in VMEM, the normalize
and every direct quantization happen in registers, and only the final
quantized-grid output is written back.  Direct quantization uses the FIXED
2^(1-k) grid step — no amax, no data-dependent rescan anywhere.

Kinds (static):
  "rms"   — per-row RMS stats (no mean, no beta):   qrmsnorm
  "layer" — per-row mean + variance:                qlayernorm
  "batch" — per-COLUMN mean + variance over the     qbatchnorm
            flattened batch axis (x arrives as (M, C), stats over M)

Output is the fp32 *grid* value (DESIGN.md §3): every intermediate lies
exactly on its fixed-point grid, so this is bit-identical to the sim-mode
composition in core/qnorm.py — validated against ref.ubn_norm_ref.

VMEM constraint: the statistics axis is held whole in each block (the
stats need every element), so the per-block footprint is
VMEM_BYTES_PER_ELEM x stats_axis x bt.  `ops.ubn_norm_op` picks a legal
`bt` that fits VMEM_LIMIT and falls back to the XLA oracle for shapes
where none does (e.g. a very large flattened batch under "batch").
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# v5e has 128 MiB of VMEM per core, and the 16 MiB scoped default cannot
# hold a whole-statistics-axis block at ResNet shapes, so the kernel asks
# for most of it.  v5e compiles of f32 blocks measured ~20 bytes per block
# element (double-buffered input and output plus the kernel's temporaries);
# the budget counts 24 to leave margin.
VMEM_LIMIT = 96 * 2 ** 20
VMEM_BYTES_PER_ELEM = 24


def _qd(x, k: int):
    """Direct quantization Q(x, k) = round(x * 2^(k-1)) / 2^(k-1) (Eq. 6)."""
    s = 2.0 ** (k - 1)
    return jnp.round(x * s) / s


def _ubn_kernel(x_ref, g_ref, b_ref, o_ref, *, kind, k_mu, k_sigma, k_bn,
                k_gamma, k_beta, eps):
    x = x_ref[...]
    axis = 0 if kind == "batch" else -1
    if kind == "rms":
        sigma = jnp.sqrt(jnp.mean(jnp.square(x), axis=axis, keepdims=True))
        xhat = x / (_qd(sigma, k_sigma) + eps)
    else:
        mu = jnp.mean(x, axis=axis, keepdims=True)
        var = jnp.mean(jnp.square(x), axis=axis, keepdims=True) \
            - jnp.square(mu)
        sigma = jnp.sqrt(jnp.maximum(var, 0.0))
        xhat = (x - _qd(mu, k_mu)) / (_qd(sigma, k_sigma) + eps)
    xhat = _qd(xhat, k_bn)                                     # Q_BN
    y = _qd(g_ref[...], k_gamma) * xhat
    if kind != "rms":
        y = y + _qd(b_ref[...], k_beta)
    o_ref[...] = y


@functools.partial(jax.jit, static_argnames=("kind", "k_mu", "k_sigma",
                                             "k_bn", "k_gamma", "k_beta",
                                             "eps", "bt", "interpret"))
def ubn_norm(x: jax.Array, gamma: jax.Array, beta: jax.Array | None, *,
             kind: str = "rms", k_mu: int = 16, k_sigma: int = 16,
             k_bn: int = 16, k_gamma: int = 8, k_beta: int = 8,
             eps: float = 2.0 ** -8, bt: int = 256,
             interpret: bool = True) -> jax.Array:
    """Fused stats + normalize + quantize over a 2-D view.

    Args:
      x: (M, N) f32 — rows are tokens for "rms"/"layer"; for "batch" the
        caller flattens all leading axes so columns are channels and the
        statistics reduce over M.
      gamma: (N,) f32 scale; beta: (N,) f32 shift (None for "rms").
      kind: "rms" | "layer" | "batch" (static; selects the stats recipe).
      k_*: paper bit widths for the five direct quantizers; eps: epsilon_q.
      bt: tile along the non-statistics axis.

    Returns:
      (M, N) f32 on the k_BN/k_gamma grid — bit-identical to the unfused
      sim-mode composition (the ref.ubn_*_ref oracles).
    """
    m, n = x.shape
    gamma = gamma.reshape(1, n)
    beta = (jnp.zeros((1, n), jnp.float32) if beta is None
            else beta.reshape(1, n))
    if kind == "batch":       # stats over M: tile columns, keep M whole
        bt = min(bt, n)
        pad = (-n) % bt
        if pad:
            x = jnp.pad(x, ((0, 0), (0, pad)))
            gamma = jnp.pad(gamma, ((0, 0), (0, pad)))
            beta = jnp.pad(beta, ((0, 0), (0, pad)))
        grid = ((n + pad) // bt,)
        xs = pl.BlockSpec((m, bt), lambda i: (0, i))
        vs = pl.BlockSpec((1, bt), lambda i: (0, i))
        out_spec, oshape = xs, (m, n + pad)
    else:                     # stats over N: tile rows, keep N whole
        bt = min(bt, m)
        pad = (-m) % bt
        if pad:
            x = jnp.pad(x, ((0, pad), (0, 0)))
        grid = ((m + pad) // bt,)
        xs = pl.BlockSpec((bt, n), lambda i: (i, 0))
        vs = pl.BlockSpec((1, n), lambda i: (0, 0))
        out_spec, oshape = xs, (m + pad, n)
    out = pl.pallas_call(
        functools.partial(_ubn_kernel, kind=kind, k_mu=k_mu,
                          k_sigma=k_sigma, k_bn=k_bn, k_gamma=k_gamma,
                          k_beta=k_beta, eps=eps),
        grid=grid,
        in_specs=[xs, vs, vs],
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct(oshape, jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",), vmem_limit_bytes=VMEM_LIMIT),
        name="ubn_norm",
        interpret=interpret,
    )(x, gamma, beta)
    return out[:m, :n]
