"""Quantized normalization layers (paper Eq. 11-13, adapted per DESIGN.md §3).

The paper quantizes BN's operands: mu -> k_mu, sigma -> k_sigma, the
normalized activation x_hat -> k_BN, gamma/beta -> k_gamma/k_beta.  All
quantizers use STE, so standard autodiff through these functions *is* the
paper's quantized backward evaluated on grid values (e1 = e0*gamma_q,
g_gamma = e1*x_hat, g_beta = e1, and the stat terms of e3's pre-image).
Q_E2 on the outgoing error is applied by the adjacent qeinsum/qconv.

RMSNorm / LayerNorm ports keep the identical bit-width recipe — RMSNorm is
BN with per-token statistics, no mean and no running stats (the paper itself
drops running stats "considering the computational cost", §IV-D).

Fused UBN (DESIGN.md §8): in native mode the whole forward chain —
statistics, normalize, and all five direct quantizations — runs as ONE
kernel pass through `kernels/ops.ubn_norm_op` instead of five XLA passes
re-materializing the activation between stages.  The fused forward is
bit-identical to the unfused composition (every direct quantizer has a
fixed pow2 step, so no amax appears anywhere), and the backward is the vjp
of the unfused body — the STE semantics are unchanged.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import ops

from . import qfuncs as qf
from .qconfig import QConfig
from .qtensor import get_quantizer, qt_carrier

Array = jax.Array

EPS_Q = 2.0 ** -8  # epsilon_q: small fixed-point value (Eq. 12)


def _qs(cfg: QConfig, t: Array, k: int) -> Array:
    """Direct-quantize with STE when quantization is on (registry-resolved;
    the "direct" quantizer's grid output is bit-identical to qf.q_direct)."""
    if not cfg.quantize or not cfg.quant_bn:
        return t
    return qf.ste(get_quantizer("direct", k), t)


def _maybe_stop(cfg: QConfig, t: Array) -> Array:
    return t if cfg.norm_full_bwd else jax.lax.stop_gradient(t)


def _fuse(cfg: QConfig) -> bool:
    return (cfg.native and cfg.quant_bn
            and getattr(cfg, "fuse_kernels", True))


# --------------------------------------------------------------------------
# unfused bodies (sim mode, and the vjp ground truth for the fused route)
# --------------------------------------------------------------------------


def _qbatchnorm_unfused(cfg: QConfig, x: Array, gamma: Array,
                        beta: Array) -> Array:
    axes = tuple(range(x.ndim - 1))
    mu = _maybe_stop(cfg, jnp.mean(x, axes))
    var = _maybe_stop(cfg, jnp.mean(jnp.square(x), axes) - jnp.square(mu))
    sigma = jnp.sqrt(jnp.maximum(var, 0.0))
    mu_q = _qs(cfg, mu, cfg.k_mu)
    sigma_q = _qs(cfg, sigma, cfg.k_sigma)
    xhat = (x - mu_q) / (sigma_q + EPS_Q)
    xhat = _qs(cfg, xhat, cfg.k_bn)                        # Q_BN
    gamma_q = _qs(cfg, gamma, cfg.k_gamma)
    beta_q = _qs(cfg, beta, cfg.k_beta)
    return gamma_q * xhat + beta_q


def _qrmsnorm_unfused(cfg: QConfig, x: Array, gamma: Array) -> Array:
    ms = _maybe_stop(cfg, jnp.mean(jnp.square(x), axis=-1, keepdims=True))
    sigma = jnp.sqrt(ms)
    sigma_q = _qs(cfg, sigma, cfg.k_sigma)
    xhat = x / (sigma_q + EPS_Q)
    xhat = _qs(cfg, xhat, cfg.k_bn)
    gamma_q = _qs(cfg, gamma, cfg.k_gamma)
    return gamma_q * xhat


def _qlayernorm_unfused(cfg: QConfig, x: Array, gamma: Array,
                        beta: Array) -> Array:
    mu = _maybe_stop(cfg, jnp.mean(x, axis=-1, keepdims=True))
    var = _maybe_stop(
        cfg, jnp.mean(jnp.square(x), axis=-1, keepdims=True) - jnp.square(mu))
    sigma = jnp.sqrt(jnp.maximum(var, 0.0))
    mu_q = _qs(cfg, mu, cfg.k_mu)
    sigma_q = _qs(cfg, sigma, cfg.k_sigma)
    xhat = (x - mu_q) / (sigma_q + EPS_Q)
    xhat = _qs(cfg, xhat, cfg.k_bn)
    gamma_q = _qs(cfg, gamma, cfg.k_gamma)
    beta_q = _qs(cfg, beta, cfg.k_beta)
    return gamma_q * xhat + beta_q


_UNFUSED = {"batch": _qbatchnorm_unfused, "layer": _qlayernorm_unfused}


# --------------------------------------------------------------------------
# fused UBN route (native mode): one kernel pass, unfused vjp
# --------------------------------------------------------------------------


def _ubn_widths(cfg: QConfig) -> dict:
    return dict(k_mu=cfg.k_mu, k_sigma=cfg.k_sigma, k_bn=cfg.k_bn,
                k_gamma=cfg.k_gamma, k_beta=cfg.k_beta, eps=EPS_Q)


@partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _fused_norm(kind: str, cfg: QConfig, x: Array, gamma: Array,
                beta: Array) -> Array:
    x2 = x.reshape((-1, x.shape[-1]))
    y = ops.ubn_norm_op(x2, gamma, beta, kind=kind, **_ubn_widths(cfg))
    return y.reshape(x.shape)


def _fused_norm_fwd(kind, cfg, x, gamma, beta):
    return _fused_norm(kind, cfg, x, gamma, beta), (x, gamma, beta)


def _fused_norm_bwd(kind, cfg, res, g):
    # the fused forward is bit-identical to the unfused body, so its vjp IS
    # the fused op's gradient (STE through every direct quantizer)
    x, gamma, beta = res
    _, vjp = jax.vjp(lambda *a: _UNFUSED[kind](cfg, *a), x, gamma, beta)
    return vjp(g)


_fused_norm.defvjp(_fused_norm_fwd, _fused_norm_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(0,))
def _fused_rmsnorm(cfg: QConfig, x: Array, gamma: Array) -> Array:
    x2 = x.reshape((-1, x.shape[-1]))
    y = ops.ubn_norm_op(x2, gamma, None, kind="rms", **_ubn_widths(cfg))
    return y.reshape(x.shape)


def _fused_rmsnorm_fwd(cfg, x, gamma):
    return _fused_rmsnorm(cfg, x, gamma), (x, gamma)


def _fused_rmsnorm_bwd(cfg, res, g):
    x, gamma = res
    _, vjp = jax.vjp(lambda *a: _qrmsnorm_unfused(cfg, *a), x, gamma)
    return vjp(g)


_fused_rmsnorm.defvjp(_fused_rmsnorm_fwd, _fused_rmsnorm_bwd)


# --------------------------------------------------------------------------
# public API
# --------------------------------------------------------------------------


def qbatchnorm(cfg: QConfig, x, gamma: Array, beta: Array) -> Array:
    """Quantized BN over all axes but the last (channel), paper Eq. 12."""
    x = qt_carrier(x)
    with jax.named_scope("ubn"):
        if _fuse(cfg):
            return _fused_norm("batch", cfg, x, gamma, beta)
        return _qbatchnorm_unfused(cfg, x, gamma, beta)


def qrmsnorm(cfg: QConfig, x, gamma: Array) -> Array:
    """Quantized RMSNorm: the BN recipe with per-token stats, no mean."""
    x = qt_carrier(x)
    with jax.named_scope("ubn"):
        if _fuse(cfg):
            return _fused_rmsnorm(cfg, x, gamma)
        return _qrmsnorm_unfused(cfg, x, gamma)


def qlayernorm(cfg: QConfig, x, gamma: Array, beta: Array) -> Array:
    """Quantized LayerNorm (per-token mean + var), same widths as BN."""
    x = qt_carrier(x)
    with jax.named_scope("ubn"):
        if _fuse(cfg):
            return _fused_norm("layer", cfg, x, gamma, beta)
        return _qlayernorm_unfused(cfg, x, gamma, beta)
