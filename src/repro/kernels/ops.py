"""jit'd dispatch wrappers: Pallas kernel on TPU backends, jnp oracle on CPU.

This container lowers Pallas TPU kernels only under interpret=True, so the
default execution path on CPU is the oracle (identical math); tests sweep
the kernels in interpret mode against the oracles.  On a TPU backend the
compiled kernels are selected automatically.

The ten dispatched ops (DESIGN.md §8 maps them onto the paper's data
paths):

  qmatmul_op         — int8 x int8 -> int32 MAC, optional fused requantize
                       epilogue emitting an int8 payload directly
  quantize_op        — fused scale/round/clip payload emission (Q/SQ)
  cq_op              — stochastic-rounding CQ payload (Eq. 7)
  dgrad_op           — backward input-error dot e4 = W^T e3 with Q_E2 fused
                       into the matmul prologue (Alg. 2)
  wgrad_op           — backward weight-gradient dot g_W = e3 x0^T, same
                       fused prologue
  ubn_norm_op        — fused UBN: statistics + normalize + the five direct
                       quantizers in one pass
  page_gather_op     — paged int8 KV-cache gather (defrag / tests; the
                       decode hot loop streams pages via paged_attention_op)
  paged_attention_op — fused paged decode attention: pages stream through
                       VMEM, the gathered KV never exists in HBM (§7)
  flash_attention_op — tiled online-softmax prefill/training attention on
                       int8 payloads, per-chunk decompositions in-register
  selective_scan_op  — SSM recurrence (fp32 VPU over gridded inputs)
"""
from __future__ import annotations

import collections
import threading

import jax
import jax.numpy as jnp

from . import autotune, ref
from .backward import bwd_dgrad, bwd_wgrad
from .page_gather import page_gather
from .paged_attention import (FLASH_VMEM_LIMIT, flash_attention,
                              paged_attention)
from .qmatmul import qmatmul
from .quantize import cq_stochastic, quantize_fused
from .selective_scan import selective_scan
from .ubn import VMEM_BYTES_PER_ELEM, VMEM_LIMIT, ubn_norm

# per op, the traced calls that took the XLA oracle on a TPU backend (shapes
# past a kernel's VMEM budget or the TPU tiling rule, or manual-TP attention
# whose amax must pmax); dispatch_report / dispatch_banner surface it, so no
# oracle route is silent
ORACLE_ON_TPU: collections.Counter = collections.Counter()
_COUNT_LOCK = threading.Lock()       # programs may trace on several threads

# per lane class, the traced quantize_op calls and their elements: "dense"
# where the minor dim fills whole 128-lane vregs, "narrow" where it is
# under 128 or ragged, so lanes are padded (trace-time count, no device code)
QUANTIZE_VIEWS: collections.Counter = collections.Counter()


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _oracle(op: str) -> None:
    if _on_tpu():
        with _COUNT_LOCK:
            ORACLE_ON_TPU[op] += 1


def qmatmul_op(a8, b8, requant_inv=None, *, lim=127.0, force_kernel=False):
    """Integer matmul, optionally with the fused requantize epilogue.

    Args:
      a8: (M, K) int8 payload; b8: (K, N) int8 payload.
      requant_inv: optional scalar f32 — combined pow2 rescale
        a_scale * b_scale / out_step.  When given, the kernel epilogue
        emits clip(round(acc * requant_inv), +-lim) int8 directly; no fp32
        carrier and no separate quantize pass exist between the MAC and
        the payload.
      lim: epilogue clip bound (2^(k-1)-1 for a k-bit payload).

    Returns:
      (M, N) int32 accumulator, or (M, N) int8 payload with requant_inv.
    """
    if _on_tpu() or force_kernel:
        tiles = autotune.tiles_for(
            "qmatmul",
            (a8.shape, str(a8.dtype), b8.shape, str(b8.dtype),
             requant_inv is not None),
            {"bm": 128, "bn": 128, "bk": 256})
        return qmatmul(a8, b8, requant_inv, lim=lim,
                       interpret=not _on_tpu(), **tiles)
    if requant_inv is None:
        return ref.qmatmul_ref(a8, b8)
    return ref.qmatmul_requant_ref(a8, b8, requant_inv, lim)


def quantize_op(x, inv_step, lim=127.0, *, force_kernel=False):
    """Fused shift/direct quantize payload emission.

    Args:
      x: f32 of any shape, on/near a fixed-point grid; inv_step: scalar f32
      exact pow2 reciprocal of the grid step; lim: clip bound.

    Returns:
      int8 payload clip(round(x * inv_step), +-lim), x's shape.  The kernel
      runs on x's (rows, minor dim) view; its lane class is counted in
      QUANTIZE_VIEWS.
    """
    lanes = "dense" if x.ndim and x.shape[-1] % 128 == 0 else "narrow"
    with _COUNT_LOCK:
        QUANTIZE_VIEWS[lanes] += 1
        QUANTIZE_VIEWS[f"{lanes}_elements"] += x.size
    if _on_tpu():
        return quantize_fused(x, inv_step, lim=lim, interpret=False)
    if force_kernel:
        return quantize_fused(x, inv_step, lim=lim, interpret=True)
    return ref.quantize_ref(x, inv_step, lim)


def cq_op(x, bits, inv_step, dr=128.0, *, force_kernel=False):
    """Stochastic-rounding CQ payload (paper Eq. 7).

    Args:
      x: (M, N) f32 gradient; bits: (M, N) uint32 random bits;
      inv_step: scalar f32 rescale; dr: dynamic-range bound.

    Returns:
      (M, N) int16 payload clip(Sr(x * inv_step), +-(dr-1)).
    """
    if _on_tpu():
        return cq_stochastic(x, bits, inv_step, dr=dr, interpret=False)
    if force_kernel:
        return cq_stochastic(x, bits, inv_step, dr=dr, interpret=True)
    return ref.cq_stochastic_ref(x, bits, inv_step, dr)


def dgrad_op(g, b8, scal, *, mode="affine", k=8, force_kernel=False):
    """Fused-prologue backward input-error dot (paper Alg. 2, e4 = W^T e3).

    Args:
      g: (M, N) f32 incoming error e2; b8: (K, N) int8 payload of the
      forward weight operand; scal: (3,) f32 [inv, s1, s2] where inv is
      the exact pow2 reciprocal of the Q_E payload step and s1/s2 are the
      per-plane output scales (plane_step * b_scale).
      mode: "affine" (SQ/grid/direct, one plane) | "flag" (Eq. 17, two
      planes); k: Q_E bit width.

    Returns:
      (M, K) f32 da — the integer dots' dequantized sum.  The error payload
      is produced inside the kernel prologue and never stored.
    """
    if _on_tpu() or force_kernel:
        tiles = autotune.tiles_for(
            "dgrad", (g.shape, b8.shape, mode, k),
            {"bm": 128, "bk": 128, "bn": 128})
        return bwd_dgrad(g, b8, scal, mode=mode, k=k,
                         interpret=not _on_tpu(), **tiles)
    return ref.dgrad_ref(g, b8, scal, mode=mode, k=k)


def wgrad_op(a8, g, scal, *, mode="affine", k=8, force_kernel=False):
    """Fused-prologue backward weight-gradient dot (Alg. 2, g_W = e3 x0^T).

    Args:
      a8: (M, K) int8 payload of the saved forward activation x0;
      g: (M, N) f32 incoming error e2; scal: (3,) f32 [inv, s1, s2]
      (s1/s2 = plane_step * a_scale); mode/k as in dgrad_op.

    Returns:
      (K, N) f32 db on the same dequantized scale as the unfused path.
    """
    if _on_tpu() or force_kernel:
        tiles = autotune.tiles_for(
            "wgrad", (a8.shape, g.shape, mode, k),
            {"bm": 128, "bk": 128, "bn": 128})
        return bwd_wgrad(a8, g, scal, mode=mode, k=k,
                         interpret=not _on_tpu(), **tiles)
    return ref.wgrad_ref(a8, g, scal, mode=mode, k=k)


# the UBN kernel holds the full statistics axis in one VMEM block (the
# stats need every element), so a block is (stats_axis x tile) and costs
# VMEM_BYTES_PER_ELEM per element.  Tiles shrink to fit the kernel's
# VMEM_LIMIT, and shapes where no legal tile fits fall back to the XLA
# oracle (counted in ORACLE_ON_TPU).


def _ubn_axes(kind: str, m: int, n: int) -> tuple[int, int, int]:
    """(statistics axis, tiled axis, TPU alignment of the tiled axis): a
    "batch" tile is the block's last dim (lanes, 128), a row tile its
    second-to-last (sublanes, 8)."""
    return (m, n, 128) if kind == "batch" else (n, m, 8)


def _ubn_legal(kind: str, m: int, n: int, bt: int) -> bool:
    """A TPU block dim is a multiple of its alignment or the whole axis
    (ubn_norm clamps bt to the axis, so anything past it is the axis)."""
    _, other, align = _ubn_axes(kind, m, n)
    return bt >= other or bt % align == 0


def _ubn_tile(kind: str, m: int, n: int) -> int | None:
    """Largest legal tile (<= 256) along the non-statistics axis whose
    block fits the VMEM budget, or None -> oracle."""
    stats, other, align = _ubn_axes(kind, m, n)
    fit = min(256, VMEM_LIMIT // (VMEM_BYTES_PER_ELEM * max(stats, 1)))
    if other <= fit:
        return other
    return (fit - fit % align) or None


def ubn_norm_op(x, gamma, beta=None, *, kind="rms", k_mu=16, k_sigma=16,
                k_bn=16, k_gamma=8, k_beta=8, eps=2.0 ** -8,
                force_kernel=False):
    """Fused UBN: statistics + normalize + output quantization, one pass.

    Args:
      x: (M, N) f32 — rows are tokens for "rms"/"layer"; for "batch" the
      caller flattens leading axes so statistics reduce over M per channel.
      gamma: (N,) f32; beta: (N,) f32 or None (rms has no shift).
      kind: "rms" | "layer" | "batch"; k_*: the paper's five norm widths;
      eps: epsilon_q (Eq. 12).

    Returns:
      (M, N) f32 on the k_BN/k_gamma grid, bit-identical to the unfused
      sim-mode composition in core/qnorm.py.  Shapes where no legal tile
      fits a VMEM block (huge flattened batch for "batch") lower through
      the XLA oracle instead — same math, counted in ORACLE_ON_TPU.
    """
    kw = dict(kind=kind, k_mu=k_mu, k_sigma=k_sigma, k_bn=k_bn,
              k_gamma=k_gamma, k_beta=k_beta, eps=eps)
    bt = _ubn_tile(kind, x.shape[0], x.shape[1])
    if bt is not None and (_on_tpu() or force_kernel):
        # the tuned tile competes with the heuristic but never exceeds
        # its VMEM-fit bound (the tile axis carries no statistics, so any
        # bt is bit-identical — tests/test_autotune.py proves it)
        tiles = autotune.tiles_for(
            "ubn_norm", (x.shape, kind), {"bt": bt})
        tuned = min(tiles["bt"], bt)
        tiles["bt"] = tuned if _ubn_legal(kind, *x.shape, tuned) else bt
        return ubn_norm(x, gamma, beta, interpret=not _on_tpu(),
                        **tiles, **kw)
    _oracle("ubn_norm")
    return ref.ubn_norm_ref(x, gamma, beta, **kw)


def page_gather_op(pages, table, *, force_kernel=False):
    """Paged int8 KV-cache gather (the serving engine's decode read).

    Args:
      pages: (P, page, *rest) int8 physical page arena; table: (B, NB)
      int32 per-lane page ids (out-of-range ids clamp; id 0 is the trash
      page dead lanes point at).

    Returns:
      (B, NB, page, *rest) int8 contiguous per-lane view — no dequantize.
      Trailing dims are flattened for the kernel and restored on the way
      out.
    """
    rest = pages.shape[2:]
    if _on_tpu() or force_kernel:
        p, page = pages.shape[:2]
        flat = pages.reshape(p, page, -1)
        out = page_gather(flat, table, interpret=not _on_tpu())
        return out.reshape(table.shape + (page,) + rest)
    return ref.page_gather_ref(pages, table)


# the decode score pass holds one lane's full (H, T) f32 score row in VMEM
# scratch; the flash kernel holds full-batch (B, H, qc, .) m/l/acc scratch.
# Shapes past these budgets, or whose blocks break the TPU tiling rule,
# lower through the XLA oracles instead (same math), counted in
# ORACLE_ON_TPU like the UBN tile guard above.
_ATTN_VMEM_BUDGET = 4 * 2 ** 20


def paged_attention_fits(kvg: int, t: int) -> bool:
    """Whether one lane's score row fits the decode kernel's VMEM scratch."""
    return 4 * kvg * t <= _ATTN_VMEM_BUDGET


def flash_attention_fits(b: int, qc: int, h: int, dh: int, kc: int) -> bool:
    """Whether the flash kernel's blocks and scratch fit its VMEM limit.
    v5e compiles measured at most (32 dh + 8 kc) bytes per (batch, head,
    query) row at dh 128."""
    return b * qc * h * (32 * dh + 8 * kc) <= FLASH_VMEM_LIMIT


def paged_attention_op(q8, k_pages, v_pages, table, q_pos, t_valid,
                       q_scale, k_scale, v_scale, *, sm_scale,
                       k_a=8, force_kernel=False):
    """Fused paged decode attention (the serving engine's decode hot loop).

    Streams int8 K/V pages through VMEM via a scalar-prefetched page table
    (two passes; the single probability amax lives between them as a scalar
    reduction over the row sums — DESIGN.md §7) and writes only the
    attention output: the gathered contiguous KV view never exists in HBM.

    Args:
      q8: (B, H, dh) int8 query payload (one decode token per lane);
      k_pages/v_pages: (P, page, KV, dh) int8 physical page arenas;
      table: (B, NB) int32 per-lane page ids (out-of-range ids clamp;
      id 0 is the trash page dead lanes point at); q_pos: (B,) int32
      per-lane positions; t_valid: scalar bound on valid positions;
      q/k/v_scale: pow2 payload scales; sm_scale: 1/sqrt(dh); k_a: the
      probability grid width.

    Returns:
      (B, H, dh) f32 pre-Q_A attention output, bit-exact against the
      unfused page_gather + decode_attention path.
    """
    page = k_pages.shape[1]
    fits = paged_attention_fits(q8.shape[1], table.shape[1] * page)
    # the score row takes one page per grid step at lane offset j * page,
    # which the TPU compiler accepts only for 128-aligned pages
    aligned = page % 128 == 0
    # under manual TP (amax_sync active) the probability amax must pmax
    # over the model axis — a mesh collective the Pallas kernel body cannot
    # issue, so sharded decode stays on the (bit-identical) oracle
    tp_sync = ref._AMAX_SYNC_AXIS is not None
    if not tp_sync and fits and (force_kernel or (_on_tpu() and aligned)):
        # the tunable here is the pipeliner's dimension_semantics hint —
        # the kv chunking itself is amax granularity (numerics), not a knob
        tiles = autotune.tiles_for(
            "paged_attention", (q8.shape, k_pages.shape, table.shape, k_a),
            {"ds": ("parallel", "arbitrary")})
        return paged_attention(q8, k_pages, v_pages, table, q_pos, t_valid,
                               q_scale, k_scale, v_scale, sm_scale=sm_scale,
                               k_a=k_a, ds=tiles["ds"],
                               interpret=not _on_tpu())
    _oracle("paged_attention")
    return ref.paged_attention_ref(q8, k_pages, v_pages, table, q_pos,
                                   t_valid, q_scale, k_scale, v_scale,
                                   sm_scale=sm_scale, k_a=k_a)


def flash_attention_op(q8, k8, v8, q_pos, k_pos, k_valid, q_scale, k_scale,
                       v_scale, *, causal, sm_scale, q_chunk, kv_chunk,
                       k_a=8, force_kernel=False):
    """Tiled online-softmax attention on int8 payloads (prefill/training).

    One (q-tile, kv-tile) grid cell per chunk pair; per-chunk GridQuantizer
    decompositions run in-register over the full batch block, so the
    output is bit-identical to the pure-JAX chunked online-softmax in
    models/layers.py (including its saturate-at-pow2-amax corner).
    Forward-only: the training backward stays on the unfused composition
    (custom_vjp in models/layers.py), whose Q_E2 semantics are Alg. 2's.

    Args:
      q8: (B, S, H, dh) int8; k8/v8: (B, T, KV, dh) int8 — pre-padded to
      chunk multiples with payload zeros; q_pos (S,) / k_pos (T,) int32;
      k_valid: (T,) int mask of real kv slots; scales: pow2 payload
      scales; causal: mask mode; sm_scale: 1/sqrt(dh); q_chunk/kv_chunk:
      tile sizes (must divide S / T).

    Returns:
      (B, S, H, dh) f32 pre-Q_A output (padded rows included).
    """
    b, s, h, dh = q8.shape
    t = k8.shape[1]
    fits = flash_attention_fits(b, q_chunk, h, dh, kv_chunk)
    # TPU tiling rule on the (q_chunk, 1) position column and the
    # (1, kv_chunk) key rows
    aligned = ((q_chunk % 8 == 0 or q_chunk == s)
               and (kv_chunk % 128 == 0 or kv_chunk == t))
    # same manual-TP routing rule as paged_attention_op: in-kernel amax
    # cannot pmax, so sharded prefill/training takes the oracle
    tp_sync = ref._AMAX_SYNC_AXIS is not None
    if not tp_sync and fits and (force_kernel or (_on_tpu() and aligned)):
        # q_chunk/kv_chunk are per-chunk amax granularity — numerics, never
        # autotuned; only the scheduling hint is a legal knob here
        tiles = autotune.tiles_for(
            "flash_attention",
            (q8.shape, k8.shape, causal, q_chunk, kv_chunk, k_a),
            {"ds": ("parallel", "arbitrary")})
        return flash_attention(q8, k8, v8, q_pos, k_pos, k_valid, q_scale,
                               k_scale, v_scale, causal=causal,
                               sm_scale=sm_scale, q_chunk=q_chunk,
                               kv_chunk=kv_chunk, k_a=k_a, ds=tiles["ds"],
                               interpret=not _on_tpu())
    _oracle("flash_attention")
    return ref.flash_attention_ref(q8, k8, v8, q_pos, k_pos, k_valid,
                                   q_scale, k_scale, v_scale, causal=causal,
                                   sm_scale=sm_scale, q_chunk=q_chunk,
                                   kv_chunk=kv_chunk, k_a=k_a)


def selective_scan_op(a, b, c, *, force_kernel=False):
    """SSM selective-scan recurrence h_t = a_t h_{t-1} + b_t; y_t = c_t·h_t.

    Args:
      a, b: (B, S, D, N) f32 gridded scan inputs; c: (B, S, N) f32.

    Returns:
      (B, S, D) f32 outputs (fp32 VPU over 16-bit-gridded inputs —
      DESIGN.md §6).
    """
    if _on_tpu():
        return selective_scan(a, b, c, interpret=False)
    if force_kernel:
        return selective_scan(a, b, c, interpret=True)
    return ref.selective_scan_ref(a, b, c)


# --------------------------------------------------------------------------
# dispatch introspection (examples' startup banners, launch/report.py)
# --------------------------------------------------------------------------

OPS = ("qmatmul", "quantize", "cq", "dgrad", "wgrad", "ubn_norm",
       "page_gather", "paged_attention", "flash_attention", "selective_scan")


def dispatch_report(cfg=None) -> dict:
    """What the ops above resolve to right now.

    Returns {"backend", "route" ("kernel" on TPU else "oracle"),
    "ops": {name: route}, "oracle_on_tpu": {name: traced calls that took
    the oracle on TPU so far}, "quantize_views": {"dense"/"narrow": traced
    quantize_op calls of that lane class, "<class>_elements": their
    elements}}; with a QConfig also "mode" and "fused" (whether native mode
    routes backward/UBN/attention through the fused ops).
    """
    route = "kernel" if _on_tpu() else "oracle"
    rep = {"backend": jax.default_backend(), "route": route,
           "ops": {name: route for name in OPS},
           "oracle_on_tpu": dict(ORACLE_ON_TPU),
           "quantize_views": dict(QUANTIZE_VIEWS)}
    rep["autotune"] = {"entries": len(autotune.entries()),
                       "dir": autotune.cache_dir()}
    from repro.runtime.compress import default_wire_codec
    codec, why = default_wire_codec(rep["backend"])
    rep["wire_codec"] = {"default": codec, "why": why}
    if cfg is not None:
        rep["mode"] = cfg.mode
        rep["fused"] = bool(cfg.native and getattr(cfg, "fuse_kernels", True))
    return rep


def dispatch_banner(cfg=None) -> str:
    """One-line startup banner, e.g.
    '[kernels] backend=tpu route=kernel oracle_on_tpu=ubn_norm:9
    mode=native bwd/ubn=fused attn=fused ...'."""
    rep = dispatch_report(cfg)
    fell = ",".join(f"{k}:{v}"
                    for k, v in sorted(rep["oracle_on_tpu"].items()))
    line = (f"[kernels] backend={rep['backend']} route={rep['route']} "
            f"oracle_on_tpu={fell or 0}")
    if cfg is not None:
        fused = "fused" if rep["fused"] else "unfused"
        line += f" mode={rep['mode']} bwd/ubn={fused} attn={fused}"
    line += " " + autotune.banner_fragment()
    wc = rep["wire_codec"]
    line += f" wire_codec={wc['default']} ({wc['why']})"
    return line


COLLECTIVE_PRIMS = frozenset({
    "ppermute", "psum", "pmax", "pmin", "all_gather", "all_to_all",
    "reduce_scatter", "psum_scatter", "all_reduce"})


def collective_eqns(jaxpr) -> list:
    """(primitive name, out shape, out dtype) for every cross-device
    collective reachable from `jaxpr` (recursing through shard_map, scan,
    custom_vjp, ...).

    The sharded-training acceptance checks are phrased over this listing
    (DESIGN.md §9): with the integer-wire gradient sync, every `ppermute`
    or `all_gather` payload must be an integer dtype and every
    floating-point reduction (`psum`/`pmax`) must be scalar-shaped — the
    wire scale pmax and the loss-metric mean.  A tensor-shaped f32 psum
    means gradients crossed devices as floats (the XLA all-reduce baseline
    the jaxpr tests use as their positive control).
    """
    return [e for e in eqns_outside_pallas(jaxpr)
            if e[0] in COLLECTIVE_PRIMS]


def eqns_outside_pallas(jaxpr, out=None) -> list:
    """(primitive name, out shape, out dtype) for every eqn reachable from
    `jaxpr`, recursing through sub-jaxprs (pjit, scan, custom_vjp, ...) but
    NOT into pallas_call bodies — those record as ("pallas_call", None,
    None).

    The fused-decode acceptance checks are phrased over this listing: a
    dense gathered-KV-shaped int8 intermediate outside a pallas body means
    the decode step took the gather-then-attend route instead of streaming
    pages through the fused attention kernel.
    """
    if out is None:
        out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append(("pallas_call", None, None))
            continue
        subs = []
        for v in eqn.params.values():
            vs = v if isinstance(v, (tuple, list)) else (v,)
            for vv in vs:
                if hasattr(vv, "eqns"):
                    subs.append(vv)
                elif hasattr(vv, "jaxpr") and hasattr(vv.jaxpr, "eqns"):
                    subs.append(vv.jaxpr)
        if subs:
            for sub in subs:
                eqns_outside_pallas(sub, out)
        else:
            aval = eqn.outvars[0].aval if eqn.outvars else None
            out.append((eqn.primitive.name,
                        getattr(aval, "shape", ()),
                        getattr(aval, "dtype", None)))
    return out
