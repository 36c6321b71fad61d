"""Expert-parallel Mixture-of-Experts FFN (capacity dispatch, shard_map EP).

Tokens are replicated across the tensor/expert axis (they already are in the
pjit TP scheme — activations enter layers replicated over "model"), experts
are sharded over it.  Each device builds capacity buffers for its local
experts only, runs the quantized expert matmuls, scatters contributions back
and psums across the expert axis.  Routing is computed identically on every
expert rank (deterministic), so no dispatch collective is needed; the only
communication is the output psum — the same all-reduce TP already pays.

The router is exempt from quantization (a softmax decision path, mirroring
the paper's first/last-layer exemption — DESIGN.md §6).
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P


from repro.core import qact, qeinsum, qt_carrier, qweight
from repro.core.qconfig import QConfig


def init_moe_params(cfg, acfg, key):
    from .layers import winit
    e, d, f = acfg.moe_experts, acfg.d_model, acfg.d_ff
    ks = jax.random.split(key, 4)
    return {
        "router": jax.random.normal(ks[0], (d, e), jnp.float32) * 0.02,
        "wg": winit(cfg, ks[1], (e, d, f), d),
        "wu": winit(cfg, ks[2], (e, d, f), d),
        "wd": winit(cfg, ks[3], (e, f, d), f),
    }


def moe_labels():
    return {"router": "exempt", "wg": "w", "wu": "w", "wd": "w"}


def moe_pspecs(dp, tp):
    return {"router": P(None, None), "wg": P(tp, None, None),
            "wu": P(tp, None, None), "wd": P(tp, None, None)}


def _moe_local(cfg: QConfig, acfg, x, rw, wg, wu, wd, e_off,
               dropless: bool = False):
    """Per-device MoE on local tokens x:(T,D) with local experts.

    `dropless` sizes capacity to worst case (cap = T*k).  Decode uses it:
    a one-token-per-lane batch is tiny, and under the serving engine's
    padded lane batches a capacity drop would let DEAD lanes displace live
    tokens from expert slots — routing must not depend on lane padding.
    """
    t, d = x.shape
    e, k = acfg.moe_experts, acfg.moe_topk
    el = wg.shape[0]
    if dropless:
        cap = t * k
    else:
        cap = max(1, int(math.ceil(t * k / e * acfg.capacity_factor)))

    logits = x @ rw                                     # router (exempt fp32)
    vals, idx = lax.top_k(logits, k)                    # (T, k)
    gates = jax.nn.softmax(vals, axis=-1)
    e_flat = idx.reshape(-1)
    g_flat = gates.reshape(-1)
    t_flat = jnp.repeat(jnp.arange(t), k)

    oh = jax.nn.one_hot(e_flat, e, dtype=jnp.int32)
    pos = jnp.take_along_axis(jnp.cumsum(oh, axis=0) - 1,
                              e_flat[:, None], axis=1)[:, 0]
    ok = (e_flat >= e_off) & (e_flat < e_off + el) & (pos < cap)
    e_loc = jnp.where(ok, e_flat - e_off, el)           # el => dropped
    pos_c = jnp.where(ok, pos, cap)

    # Inverse dispatch map (el, cap): which token fills each capacity slot.
    # Gathering x through it builds the (el, cap, d) buffer directly —
    # never materializing the (T*k, d) token copies (memory term, §Perf).
    tid = jnp.zeros((el + 1, cap + 1), jnp.int32)
    tid = tid.at[e_loc, pos_c].set(t_flat, mode="drop")
    gbuf = jnp.zeros((el + 1, cap + 1), x.dtype)
    gbuf = gbuf.at[e_loc, pos_c].set(jnp.where(ok, g_flat, 0.0), mode="drop")
    tid, gbuf = tid[:el, :cap], gbuf[:el, :cap]
    xbuf = x[tid] * (gbuf != 0)[..., None]

    # quantized expert matmuls (SwiGLU)
    gate = qact(cfg, acfg.act,
                qeinsum(cfg, "ecd,edf->ecf", "default", True, xbuf, qweight(cfg, wg)))
    up = qact(cfg, "none",
              qeinsum(cfg, "ecd,edf->ecf", "default", True, xbuf, qweight(cfg, wu)))
    h = qact(cfg, "none", gate * up)
    ybuf = qeinsum(cfg, "ecf,efd->ecd", "default", True, h, qweight(cfg, wd))

    # combine: scatter-add weighted expert outputs back to tokens (slots
    # with gate 0 scatter zeros to token 0 — harmless)
    y = jnp.zeros((t, d), x.dtype)
    y = y.at[tid].add(ybuf * gbuf[..., None], mode="drop")
    return y


def moe_ffn(cfg: QConfig, acfg, x, p, mesh=None, dp_axes=("data",),
            tp_axis="model", tp_size: int = 1):
    """x: (B, S, D) on the activation grid -> (B, S, D).

    QTensor inputs degrade to their grid carrier here: the capacity
    dispatch (gather + gate mask) and shard_map specs operate on flat fp32;
    the expert matmuls re-enter the integer path via qeinsum/qweight.

    Three parallelism regimes:
      mesh given        — this function owns a shard_map (pjit callers).
      tp_size > 1       — manual expert parallelism INSIDE an enclosing
                          full-manual shard_map (the sharded train step):
                          expert params arrive pre-sliced over `tp_axis`,
                          routing is computed identically on every rank,
                          and the caller's tp_exit psums the partial
                          outputs.  The router is replicated, so its
                          cotangent (partial per rank: only local experts'
                          gate paths) re-enters through tp_enter.
      neither           — single-device local MoE.
    """
    x = qt_carrier(x)
    b, s, d = x.shape
    x2 = x.reshape(b * s, d)

    dropless = s == 1                   # decode: see _moe_local docstring
    if tp_size > 1:
        from .layers import tp_enter
        el = p["wg"].shape[0]           # local expert count (pre-sliced)
        e_off = lax.axis_index(tp_axis) * el
        y = _moe_local(cfg, acfg, x2, tp_enter(tp_axis, p["router"]),
                       p["wg"], p["wu"], p["wd"], e_off, dropless=dropless)
        return y.reshape(b, s, d)       # partial; caller's tp_exit psums
    if mesh is None or tp_axis not in mesh.axis_names:
        y = _moe_local(cfg, acfg, x2, p["router"], p["wg"], p["wu"], p["wd"],
                       e_off=0, dropless=dropless)
        return y.reshape(b, s, d)

    el = acfg.moe_experts // mesh.shape[tp_axis]

    def f(x2, rw, wg, wu, wd):
        e_off = lax.axis_index(tp_axis) * el
        y = _moe_local(cfg, acfg, x2, rw, wg, wu, wd, e_off,
                       dropless=dropless)
        return lax.psum(y, tp_axis)

    fn = jax.shard_map(
        f, mesh=mesh,
        in_specs=(P(dp_axes, None), P(None, None), P(tp_axis, None, None),
                  P(tp_axis, None, None), P(tp_axis, None, None)),
        out_specs=P(dp_axes, None), check_vma=False)
    y = fn(x2, p["router"], p["wg"], p["wu"], p["wd"])
    return y.reshape(b, s, d)
