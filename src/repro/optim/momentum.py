"""Quantized Momentum optimizer + fixed-point updates (paper Eq. 19-24).

Per training step i and layer l:
    g_q    = CQ(g_W)            (weights, Eq. 5/18 — stochastic rounding)
           = Q(g, 15)           (gamma/beta, Eq. 18)
    Acc_i  = Mom * Acc_{i-1,q} + g_q          (Eq. 20)
    Acc_iq = Q(Acc_i, k_Acc)
    dW     = lr * Acc_i                        (Eq. 23, lr on the k_lr grid)
    W     <- clip(Q(W - dW, k_WU), +-(1 - 2^-(k_WU-1)))

Bit-width closure (Eq. 22/24) is asserted by QConfig.validate().

Leaves are classified by a `labels` pytree of strings:
    "w"      — matmul/conv weights: CQ gradient quantization
    "gamma" / "beta" — norm parameters: direct 15-bit gradient quantization
    "exempt" — first/last layers & any fp32-kept leaf: vanilla momentum
"""
from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro.core import qfuncs as qf
from repro.core.qconfig import QConfig
from repro.core.qtensor import get_quantizer


class MomentumState(NamedTuple):
    acc: Any           # pytree like params
    step: jax.Array    # int32 scalar


def fixed_point_lr(lr: float, cfg: QConfig) -> float:
    """Learning rate on the k_lr-bit grid (e.g. 0.05 -> 26*2^-9)."""
    if not cfg.quantize:
        return lr
    s = 2.0 ** (cfg.k_lr - 1)
    return max(round(lr * s), 1.0) / s


def dr_bits_schedule(step: int | jax.Array, boundaries=(), base_bits: int = 8):
    """dr = 2^(k-1) shrinks at step boundaries (paper §III-C: k 8 -> 7 ...).

    `base_bits` is cfg.k_gw in the train drivers; with boundaries=() the
    schedule is constant at the base (drivers plumb --dr-boundaries — see
    parse_boundaries — and rebuild/re-select the step fn at each boundary,
    since dr_bits is a static trace constant).

    Static python int when `step` is concrete; for traced steps the caller
    should pass the schedule value in as a static per-epoch constant.
    """
    bits = base_bits
    for b in boundaries:
        if step >= b:
            bits -= 1
    return max(bits, 2)


def parse_boundaries(spec: str) -> tuple[int, ...]:
    """--dr-boundaries CLI format: '200,400' -> (200, 400), '' -> ()."""
    return tuple(int(s) for s in str(spec).split(",") if s.strip())


def _grad_quantizer(cfg: QConfig, dr_bits: int):
    """Resolve cfg.g through the registry, honoring its static params.

    The per-step dr schedule and the legacy stochastic_g knob are injected
    only where the registered quantizer declares those fields AND the spec
    did not pin them explicitly — an explicit QuantSpec param is
    authoritative (e.g. params=(("stochastic", False),) opts out of both
    stochastic rounding and the schedule default)."""
    import dataclasses
    params = dict(cfg.g.params)
    fields = {f.name for f in
              dataclasses.fields(type(get_quantizer(cfg.g.kind, cfg.g.k,
                                                    cfg.g.params)))}
    if "dr_bits" in fields:
        params.setdefault("dr_bits", dr_bits)
    if "stochastic" in fields:
        params.setdefault("stochastic", cfg.stochastic_g)
    return get_quantizer(cfg.g.kind, cfg.g.k, tuple(sorted(params.items())))


def init_momentum(params: Any) -> MomentumState:
    acc = jax.tree.map(jnp.zeros_like, params)
    return MomentumState(acc=acc, step=jnp.zeros((), jnp.int32))


def _mom_coeff(cfg: QConfig, mom: float) -> float:
    if not cfg.quantize:
        return mom
    s = 2.0 ** (cfg.k_mom - 1)
    return round(mom * s) / s          # e.g. 0.75 = 3 * 2^-2 (3-bit)


def _plain_path(cfg: QConfig, lab) -> bool:
    """Vanilla-momentum leaves: fp32 config, exempt leaves, or Table II runs
    with both the G and U quantizers off."""
    return (not cfg.quantize or lab == "exempt"
            or not (cfg.quant_g or cfg.quant_u))


def quantize_grad_leaf(cfg: QConfig, g, lab, key, dr_bits: int | None = None):
    """Per-leaf gradient quantization (Eq. 18): CQ for "w" leaves, direct
    15-bit for gamma/beta, identity for plain-path leaves.

    Split from `apply_leaf_update` so ZeRO-sharded optimizers can quantize
    the FULL leaf (CQ's amax scale and stochastic-rounding bits are
    leaf-global — a chunk-local quantization would make the update depend
    on the chunking) and then update only their chunk of (p, gq, acc).
    """
    if _plain_path(cfg, lab) or not cfg.quant_g:
        return g
    if dr_bits is None:        # unscheduled callers: cfg.k_gw IS the dr width
        dr_bits = cfg.k_gw
    with jax.named_scope("cq"):
        if lab == "w":
            # registry-resolved gradient quantizer (cfg.g names kind, k_gc
            # and static params); the dr schedule and rounding mode are
            # per-step parameters injected only when the registered
            # quantizer declares those fields (i.e. CQ-family kinds)
            return _grad_quantizer(cfg, dr_bits)(g, key=key)
        if lab in ("gamma", "beta"):
            k = cfg.k_ggamma if lab == "gamma" else cfg.k_gbeta
            return get_quantizer("direct", k)(g)
    raise ValueError(f"unknown label {lab!r}")


def apply_leaf_update(cfg: QConfig, p, gq, a, lab, lr, mom: float = 0.75):
    """Elementwise Momentum + fixed-point update (Eq. 19-24) given the
    already-quantized gradient `gq`.  Returns (new_p, new_acc).

    Every operation is elementwise, so this applies bit-identically to any
    aligned chunking of (p, gq, a) — the property the ZeRO-1 sharded update
    in launch/train.py relies on (tests/test_sharded_train.py).
    """
    with jax.named_scope("update"):
        if _plain_path(cfg, lab) or not cfg.quant_u:
            # plain momentum (raw mom coefficient; Table II FP32-update runs)
            acc = mom * a + gq
            return p - lr * acc, acc
        momq = _mom_coeff(cfg, mom)
        acc_full = momq * qf.q_direct(a, cfg.k_acc) + gq      # Eq. 20
        acc = qf.q_direct(acc_full, cfg.k_acc)
        dw = lr * acc_full                                    # Eq. 23
        q = qf.q_direct(p - dw, cfg.k_wu)                     # k_WU grid
        lim = 1.0 - 2.0 ** (1 - cfg.k_wu)
        return jnp.clip(q, -lim, lim), acc


def momentum_update(cfg: QConfig, params: Any, grads: Any, state: MomentumState,
                    labels: Any, key: jax.Array, lr: float | jax.Array,
                    mom: float = 0.75, dr_bits: int | None = None):
    """One optimizer step.  Returns (new_params, new_state).

    `lr` must already be on the k_lr grid (see fixed_point_lr); `dr_bits` is
    the (static) CQ range schedule value for this step — None takes
    cfg.k_gw, the schedule base.
    """
    leaves, treedef = jax.tree.flatten(params)
    glist = treedef.flatten_up_to(grads)
    alist = treedef.flatten_up_to(state.acc)
    llist = treedef.flatten_up_to(labels)

    new_p, new_a = [], []
    with jax.named_scope("momentum_update"):
        for i, (p, g, a, lab) in enumerate(zip(leaves, glist, alist,
                                               llist)):
            gq = quantize_grad_leaf(cfg, g, lab, jax.random.fold_in(key, i),
                                    dr_bits)
            q, acc = apply_leaf_update(cfg, p, gq, a, lab, lr, mom)
            new_p.append(q)
            new_a.append(acc)

    return (jax.tree.unflatten(treedef, new_p),
            MomentumState(acc=jax.tree.unflatten(treedef, new_a),
                          step=state.step + 1))
