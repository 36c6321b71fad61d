"""Train-step / serve-step builders + the CLI training driver.

`make_train_step` closes the full WAGEUBN loop: quantized forward, quantized
backward (inside the model's custom vjps), CQ/Q gradient quantization +
quantized Momentum + fixed-point update (inside the optimizer).  Stochastic
rounding keys derive from the step counter => bit-exact restart.

`make_sharded_train_step` is the DP×TP production step (DESIGN.md §9): one
full-manual shard_map over a ("data", "model") mesh whose gradient sync
rides the integer wire (runtime/compress.wire_sync_mean) instead of XLA's
f32 all-reduce.  The training algorithm is parameterized by `n_shards` (the
quantization granularity — how many virtual batch shards the step computes
independently before the exact integer reduction), NOT by the device count:
running the same (global batch, n_shards) on 1 device or on dp devices
produces bit-identical weights (tests/test_sharded_train.py).
"""
from __future__ import annotations

import argparse
import time
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs import get as get_arch
from repro.core.qconfig import preset
from repro.models import build_model
from repro.optim import (apply_leaf_update, dr_bits_schedule, fixed_point_lr,
                         init_momentum, momentum_update, parse_boundaries,
                         quantize_grad_leaf)

SEED = 17


def make_task(acfg, batch: int, seq: int):
    """The seeded synthetic task for `acfg`'s family: images of the
    configured size and class count for the resnet family (`seq` unused),
    token rows otherwise."""
    from repro.data import ImageTask, TokenTask
    if acfg.family == "resnet":
        return ImageTask(img_size=acfg.img_size,
                         num_classes=acfg.num_classes, global_batch=batch)
    return TokenTask(vocab=acfg.vocab, seq_len=seq, global_batch=batch)


def make_train_step(model, qcfg, labels_tree, lr=0.05, mom=0.75,
                    dr_bits: int | None = None, n_micro: int = 1):
    """n_micro > 1 accumulates gradients over microbatches (lax.scan) —
    activation memory scales down by n_micro while the numeric result is
    the mean-of-microbatch gradients (the paper's G of the full batch).

    dr_bits: static CQ range width for this trace (None = qcfg.k_gw, the
    schedule base) — drivers with --dr-boundaries build one step fn per
    scheduled width."""
    lrq = fixed_point_lr(lr, qcfg)

    def train_step(params, opt_state, batch, step_idx):
        key = jax.random.fold_in(jax.random.PRNGKey(SEED), step_idx)
        if n_micro == 1:
            (loss, metrics), grads = jax.value_and_grad(
                model.loss, has_aux=True)(params, batch, key)
        else:
            mb = jax.tree.map(
                lambda x: x.reshape((n_micro, x.shape[0] // n_micro)
                                    + x.shape[1:]), batch)
            if getattr(model, "mesh", None) is not None:
                # anchor the microbatch layout: leading dim unsharded, batch
                # over dp (3-axis meshes mis-partition the reshape+slice)
                from jax.sharding import NamedSharding, PartitionSpec as PS
                mb = jax.tree.map(
                    lambda x: jax.lax.with_sharding_constraint(
                        x, NamedSharding(model.mesh,
                                         PS(None, model.dp,
                                            *((None,) * (x.ndim - 2))))),
                    mb)

            def acc_step(g_acc, b_i):
                (l, _), g = jax.value_and_grad(
                    model.loss, has_aux=True)(params, b_i, key)
                return jax.tree.map(jnp.add, g_acc, g), l

            g0 = jax.tree.map(jnp.zeros_like, params)
            grads, losses = jax.lax.scan(acc_step, g0, mb)
            grads = jax.tree.map(lambda g: g / n_micro, grads)
            loss = jnp.mean(losses)
            metrics = {"loss": loss}
        params, opt_state = momentum_update(
            qcfg, params, grads, opt_state, labels_tree,
            jax.random.fold_in(key, 1), lrq, mom=mom, dr_bits=dr_bits)
        return params, opt_state, metrics

    return train_step


# --------------------------------------------------------------------------
# sharded DP×TP training step (shard_map + integer-wire gradient sync)
# --------------------------------------------------------------------------


def _pad_flat(x, n: int):
    flat = x.reshape(-1)
    return jnp.pad(flat, (0, n - flat.size)) if flat.size < n else flat


def _quant_update_leaf(cfg, lab) -> bool:
    """Leaves whose updated values land on the k_WU grid (Eq. 24) — these
    all-gather as integer payloads in the ZeRO-1 layout."""
    return cfg.quantize and lab != "exempt" and cfg.quant_u


def _zero1_update(cfg, params, grads, state, labels, key, lr, mom, dr_bits,
                  dp: int):
    """ZeRO-1 Momentum step inside the shard_map body.

    The accumulator lives as flat per-device chunks (launch/shard.py); the
    gradient is quantized on the FULL leaf (CQ amax + stochastic bits are
    leaf-global), then each device applies the elementwise update to its
    chunk only and the updated chunks all-gather back — as int32 payloads on
    the fixed 2^(1-k_WU) grid for quantized leaves (exact: the update
    already lands on that grid), fp32 for exempt leaves.  Bit-identical to
    the replicated `momentum_update` by the elementwise-chunking argument in
    optim/momentum.py.
    """
    from repro.optim import MomentumState

    r = lax.axis_index("data")
    leaves, treedef = jax.tree.flatten(params)
    glist = treedef.flatten_up_to(grads)
    alist = treedef.flatten_up_to(state.acc)
    llist = treedef.flatten_up_to(labels)
    new_p, new_a = [], []
    for i, (p, g, a, lab) in enumerate(zip(leaves, glist, alist, llist)):
        gq = quantize_grad_leaf(cfg, g, lab, jax.random.fold_in(key, i),
                                dr_bits)
        c = a.shape[0]                       # local chunk length
        p_c = lax.dynamic_slice(_pad_flat(p, dp * c), (r * c,), (c,))
        g_c = lax.dynamic_slice(_pad_flat(gq, dp * c), (r * c,), (c,))
        q_c, a_c = apply_leaf_update(cfg, p_c, g_c, a, lab, lr, mom)
        if _quant_update_leaf(cfg, lab):     # k_WU grid -> integer gather
            step = 2.0 ** (1 - cfg.k_wu)
            data = jnp.round(q_c / step).astype(jnp.int32)
            full = lax.all_gather(data, "data", axis=0).reshape(-1)
            full = full.astype(jnp.float32) * step
        else:
            full = lax.all_gather(q_c, "data", axis=0).reshape(-1)
        new_p.append(full[: p.size].reshape(p.shape))
        new_a.append(a_c)
    return (jax.tree.unflatten(treedef, new_p),
            MomentumState(acc=jax.tree.unflatten(treedef, new_a),
                          step=state.step + 1))


def make_sharded_train_step(model, qcfg, labels_tree, mesh, params, *,
                            lr=0.05, mom=0.75, dr_bits: int | None = None,
                            n_shards: int | None = None, wire_bits: int = 16,
                            grad_sync: str = "int_ring",
                            wire_codec: str = "packed",
                            opt_shard: str = "replicated"):
    """DP×TP shard_map training step over a ("data", "model") mesh.

    Args:
      model: built with tp_size == mesh model-axis size (build_model).
      params: a concrete (global) param tree — used only to derive the
        partition specs; pass the tree you will train.
      n_shards: virtual batch shards (quantization granularity).  Default
        dp.  Must be a multiple of dp; the global batch must divide by it.
      wire_bits: integer wire width for gradient sync (4/8/16/32).  Sub-8
        widths at fan-ins past the classic bound ride staged int16 hops
        (runtime/compress.wire_plan) with the same exact-sum guarantee.
      grad_sync: "int_ring" (integer wire, DP-invariant) or "psum" (XLA
        fp32 all-reduce baseline — the thing the jaxpr tests prove the
        int_ring path does NOT contain).
      wire_codec: "packed" (wire_sync_tree: one stacked pmax, fused
        pre-sum, single double-buffered ring whose int8 hops pack
        two-per-int16 — DESIGN.md §13) or "leaf" (per-leaf
        wire_sync_mean rings — the pre-codec wire, kept for the
        train/wire_codec bench comparison); "auto" picks per backend
        (runtime/compress.default_wire_codec: packed on TPU, leaf on CPU
        where XLA serializes ppermutes).  Bitwise-identical results.
      opt_shard: "replicated" | "zero1" (Momentum accumulator sharded over
        data as flat chunks; requires tp == 1; see launch/shard.py).

    Returns (step_fn, state_specs): call `jax.jit(step_fn)` on arrays
    placed per state_specs — a dict with "params"/"opt"/"batch" spec trees
    (launch/shard.shard_arrays places them).

    Invariance contract (DESIGN.md §9): each virtual shard's forward and
    backward runs shard-locally (per-shard amax granularity; the fused
    Pallas kernels stay legal because no collective ever appears inside a
    kernel body); the ONE cross-device scale reduction is wire_sync_mean's
    lax.pmax, and every gradient reduction that crosses devices is an exact
    integer sum — so weights after the step are a pure function of
    (global batch, n_shards), not of the device layout.
    """
    from repro.launch import shard as S
    from repro.runtime.compress import (default_wire_codec, wire_sync_mean,
                                        wire_sync_tree)

    if wire_codec == "auto":
        wire_codec, _ = default_wire_codec()
    dp, tp = S.mesh_dims(mesh)
    if getattr(model, "tp_size", 1) != tp:
        raise ValueError(f"model.tp_size={getattr(model, 'tp_size', 1)} "
                         f"!= mesh model axis {tp}")
    if opt_shard == "zero1" and tp != 1:
        raise ValueError("opt_shard='zero1' requires tp == 1")
    n_shards = dp if n_shards is None else n_shards
    if n_shards % dp:
        raise ValueError(f"n_shards={n_shards} must be a multiple of dp={dp}")
    vs_local = n_shards // dp
    lrq = fixed_point_lr(lr, qcfg)

    def sync_grads(grads):
        if grad_sync != "int_ring":                     # f32-wire baseline
            return jax.tree.map(
                lambda g: lax.pmean(jnp.mean(g, axis=0), "data"), grads)
        if wire_codec == "packed":
            return wire_sync_tree(grads, "data", n_shards=n_shards,
                                  n_dev=dp, bits=wire_bits)
        return jax.tree.map(                            # per-leaf rings
            lambda g: wire_sync_mean(g, "data", n_shards=n_shards,
                                     n_dev=dp, bits=wire_bits), grads)

    def body(params, opt_state, batch, step_idx):
        key = jax.random.fold_in(jax.random.PRNGKey(SEED), step_idx)

        def per_vshard(b_i):
            (l, _), g = jax.value_and_grad(
                model.loss, has_aux=True)(params, b_i, key)
            return l, g

        b_local = jax.tree.leaves(batch)[0].shape[0]
        if b_local % vs_local:
            raise ValueError(
                f"global batch {b_local * dp} must divide by "
                f"n_shards={n_shards} (dp={dp}, {vs_local} virtual shards "
                f"per device, local batch {b_local})")
        # (b_local, ...) -> (vs_local, b_vshard, ...): row-major, so virtual
        # shard v always covers the same global batch rows on any layout
        vb = jax.tree.map(
            lambda x: x.reshape((vs_local, x.shape[0] // vs_local)
                                + x.shape[1:]), batch)
        # lax.map (not vmap): each virtual shard traces the same unbatched
        # program a single-device run would, keeping per-shard f32 reduction
        # shapes layout-independent — the bit-exactness contract needs that
        losses, grads = lax.map(per_vshard, vb)
        grads = sync_grads(grads)
        loss = lax.pmean(jnp.mean(losses), "data")
        okey = jax.random.fold_in(key, 1)
        if opt_shard == "zero1":
            with jax.named_scope("momentum_update"):
                params2, opt2 = _zero1_update(
                    qcfg, params, grads, opt_state, labels_tree, okey, lrq,
                    mom, dr_bits, dp)
        else:
            params2, opt2 = momentum_update(
                qcfg, params, grads, opt_state, labels_tree, okey, lrq,
                mom=mom, dr_bits=dr_bits)
        return params2, opt2, {"loss": loss}

    pspecs = S.tp_param_specs(model, params)
    ospecs = (S.zero_opt_specs(params) if opt_shard == "zero1"
              else S.opt_specs(pspecs))
    # zero1 implies tp == 1, where pspecs is already the all-replicated
    # tree — params come back replicated either way
    step_fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(pspecs, ospecs, jax.sharding.PartitionSpec("data"),
                  jax.sharding.PartitionSpec()),
        out_specs=(pspecs, ospecs, jax.sharding.PartitionSpec()),
        check_vma=False)
    specs = {"params": pspecs, "opt": ospecs,
             "batch": jax.sharding.PartitionSpec("data")}
    return step_fn, specs


def make_serve_step(model):
    def serve_step(params, cache, tokens):
        return model.serve_step(params, cache, tokens)
    return serve_step


def make_paged_decode_step(model, sampler, k_scale=None, v_scale=None,
                           key=None):
    """Fused continuous-batching decode step for the serving engine.

    step(params, slots, k_pages, v_pages, table, tokens, ctr) ->
    (new_slots, new_k_pages, new_v_pages, tokens).  One trace serves every
    engine step: the lane batch is padded to max_lanes, pages/table drive
    the paged attention, and the sampler picks next tokens on device.
    k_scale/v_scale are the pool's per-layer pow2 scales and `key` the
    base PRNG key — all closed over so the engine can donate the page
    buffers, and so the per-step sampling key derives INSIDE the fused
    trace (fold_in of `ctr`, the engine's sampling counter) instead of as
    a separately dispatched host-side computation per step.
    For non-paged families (SSM) the page arrays pass through untouched.
    """
    paged = model.decode_state_spec()["kv_layers"] > 0
    key = jax.random.PRNGKey(0) if key is None else key

    def step(params, slots, k_pages, v_pages, table, tokens, ctr):
        view = None
        if paged:
            view = {"k_pages": k_pages, "v_pages": v_pages,
                    "k_scale": k_scale, "v_scale": v_scale, "table": table}
        logits, new_slots, new_pages = model.paged_decode_step(
            params, slots, view, tokens)
        toks = sampler(logits, jax.random.fold_in(key, ctr))
        if paged:
            return new_slots, new_pages["k_pages"], new_pages["v_pages"], \
                toks
        return new_slots, k_pages, v_pages, toks

    return step


def make_chunked_prefill_step(model, chunk_pages: int, k_scale=None,
                              v_scale=None):
    """Chunked-prefill step for the serving engine (DESIGN.md §10).

    step(params, dense, k_pages, v_pages, table_row, tokens, start_page,
    n_pages) -> (dense, k_pages, v_pages, last_logits, page_snaps).

    ONE jit-stable trace processes up to `chunk_pages` FULL pages of a
    single lane's prompt: tokens is a fixed (chunk_pages * page,) block,
    `start_page` the first logical block index, `n_pages` the total full
    prompt pages — pages past it are masked (their table view zeroes to
    the trash page and their state/logit updates are discarded), so the
    same trace serves every chunk including the ragged last one.  The
    pages advance via an in-trace lax.scan — no host round-trip per page —
    and each page's numerics are scoped to that page (the radix cache's
    bitwise-determinism unit).  `page_snaps` stacks the dense state AFTER
    each page (leading axis chunk_pages): the page-boundary snapshots the
    radix tree stores for recurrent families.  `last_logits` carries the
    final ACTIVE page's last-token logits for first-token sampling of
    page-aligned prompts.
    """
    paged = model.decode_state_spec()["kv_layers"] > 0

    def step(params, dense, k_pages, v_pages, table_row, tokens,
             start_page, n_pages):
        page = tokens.shape[0] // chunk_pages
        toks = tokens.reshape(chunk_pages, page)

        def body(carry, inp):
            dn, kp, vp, lg = carry
            j, tj = inp
            active = start_page + j < n_pages
            view = None
            if paged:
                eff = jnp.where(active, table_row,
                                jnp.zeros_like(table_row))
                view = {"k_pages": kp, "v_pages": vp, "k_scale": k_scale,
                        "v_scale": v_scale, "table": eff}
            lg2, dn2, pages = model.prefill_page(
                params, dn, view, tj, (start_page + j) * page)
            dn2 = jax.tree.map(lambda a, b: jnp.where(active, a, b),
                               dn2, dn)
            lg = jnp.where(active, lg2, lg)
            if paged:
                kp, vp = pages["k_pages"], pages["v_pages"]
            return (dn2, kp, vp, lg), dn2

        lg0 = jnp.zeros((1, model.a.vocab_padded), jnp.float32)
        (dn, kp, vp, lg), snaps = lax.scan(
            body, (dense, k_pages, v_pages, lg0),
            (jnp.arange(chunk_pages), toks))
        return dn, kp, vp, lg, snaps

    return step


def make_prefill_token_step(model, k_scale=None, v_scale=None):
    """Single-token prefill append for the ragged prompt tail (< one page).

    step(params, dense, k_pages, v_pages, table_row, token, pos) ->
    (dense, k_pages, v_pages, logits).  Reuses the model's fused decode
    body at B=1 — writes the token's KV at `pos` through the lane's table
    row and advances recurrent state — but sampling stays with the caller
    (only the LAST tail token's logits feed the first sample).  One trace
    regardless of tail length; position-deterministic, so tail tokens
    inherit the same recompute-exactness as full pages (they are simply
    never published to the radix tree).
    """
    paged = model.decode_state_spec()["kv_layers"] > 0

    def step(params, dense, k_pages, v_pages, table_row, token, pos):
        slots = dict(dense, pos=pos)
        view = None
        if paged:
            view = {"k_pages": k_pages, "v_pages": v_pages,
                    "k_scale": k_scale, "v_scale": v_scale,
                    "table": table_row}
        logits, new_slots, pages = model.paged_decode_step(
            params, slots, view, token)
        new_dense = dict(new_slots, pos=dense["pos"])   # engine owns pos
        if paged:
            return new_dense, pages["k_pages"], pages["v_pages"], logits
        return new_dense, k_pages, v_pages, logits

    return step


def tp_serving_wrap(fn, mesh, in_specs, out_specs):
    """Manual-TP wrapper for a serving step function (DESIGN.md §12):
    shard_map over the same ("data", "model") mesh as training, with the
    sharded-decode contexts baked into the body — amax_sync (every
    quantizer scale becomes the global tp=1 value via a scalar pmax) and
    tp_int_wire (tp_exit reductions ride integer all_gathers).  The
    contexts are entered inside the body, so every retrace re-applies
    them; at trace time they cost nothing when tp == 1."""
    from repro.core import qfuncs as qf
    from repro.models import layers as mlayers

    from . import shard as S

    def body(*args):
        with qf.amax_sync(S.MODEL_AXIS), mlayers.tp_int_wire():
            return fn(*args)

    return jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def make_prefill(model, shape_name):
    from repro.configs.base import LM_SHAPES
    s, b, _ = LM_SHAPES[shape_name]
    a = model.a

    if a.family == "encdec":
        def prefill(params, frames):
            return model.prefill(params, frames, s // a.tgt_ratio)
        return prefill
    if a.family == "ssm":
        def prefill(params, tokens):
            return model.prefill(params, tokens)
        return prefill

    def prefill(params, tokens):
        return model.prefill(params, tokens, s)
    return prefill


# --------------------------------------------------------------------------
# CLI driver (CPU-scale smoke training with the full substrate engaged)
# --------------------------------------------------------------------------


def main(argv=None):
    p = argparse.ArgumentParser("repro.launch.train")
    from repro.core.qconfig import PRESETS
    p.add_argument("--arch", required=True)
    p.add_argument("--preset", default="full8",
                   choices=sorted(PRESETS))
    p.add_argument("--mode", default="sim", choices=["fp32", "sim", "native"])
    p.add_argument("--steps", type=int, default=50)
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--seq", type=int, default=64,
                   help="token sequence length (ignored by the resnet "
                        "family, which trains on images)")
    p.add_argument("--lr", type=float, default=0.05)
    p.add_argument("--reduced", action="store_true",
                   help="use the reduced smoke config (CPU scale)")
    p.add_argument("--ckpt-dir", default="")
    p.add_argument("--save-every", type=int, default=25)
    p.add_argument("--dp", type=int, default=1,
                   help="data-parallel mesh size (dp*tp > 1 engages the "
                        "shard_map step with integer-wire gradient sync)")
    p.add_argument("--tp", type=int, default=1,
                   help="tensor-parallel mesh size (transformer families)")
    p.add_argument("--n-shards", type=int, default=0,
                   help="virtual batch shards (quantization granularity); "
                        "0 = dp")
    p.add_argument("--wire-bits", type=int, default=16,
                   choices=[4, 8, 16, 32],
                   help="integer wire width for sharded gradient sync "
                        "(sub-8 widths stage onto int16 hops past the "
                        "classic fan-in bound)")
    p.add_argument("--grad-sync", default="int_ring",
                   choices=["int_ring", "psum"])
    p.add_argument("--wire-codec", default="auto",
                   choices=["auto", "packed", "leaf"],
                   help="int_ring codec: 'packed' = whole-tree sync (one "
                        "stacked pmax, fused pre-sum, double-buffered ring "
                        "with two-per-int16 hops at 8-bit); 'leaf' = "
                        "per-leaf rings (pre-codec wire); 'auto' = packed "
                        "on TPU, leaf on CPU (serialized-ppermute caveat)")
    p.add_argument("--dr-boundaries", default="",
                   help="comma-separated steps where CQ's dr width shrinks "
                        "one bit (paper §III-C), e.g. '30,40'; base width "
                        "is the preset's k_gw")
    p.add_argument("--opt-shard", default="replicated",
                   choices=["replicated", "zero1"])
    p.add_argument("--elastic", action="store_true",
                   help="drive the run through the ElasticRunner (async "
                        "QTensor checkpoints, restore-on-failure, bit-exact "
                        "DP reshard on membership change); requires "
                        "--ckpt-dir")
    p.add_argument("--resume", action="store_true",
                   help="resume from the latest checkpoint in --ckpt-dir "
                        "(elastic: works even if it was written under a "
                        "different --dp, as long as --n-shards matches)")
    p.add_argument("--rebalance-flags", type=int, default=0,
                   help="elastic: shrink dp to the next divisor of n_shards "
                        "after this many straggler flags (0 = off)")
    args = p.parse_args(argv)
    from repro.launch.cache import use_compile_cache
    use_compile_cache()

    acfg = get_arch(args.arch)
    if args.reduced:
        acfg = acfg.reduced()
    qcfg = preset(args.preset, args.mode if args.preset != "fp32" else None)
    from repro.kernels.ops import dispatch_banner
    print(dispatch_banner(qcfg))
    from repro.runtime.compress import default_wire_codec
    if args.wire_codec == "auto":
        codec, codec_why = default_wire_codec()
    else:
        codec, codec_why = args.wire_codec, "forced by --wire-codec"
    bounds = parse_boundaries(args.dr_boundaries)
    if bounds and args.elastic:
        p.error("--dr-boundaries is not supported under --elastic yet")
    sharded = args.dp * args.tp > 1
    model = build_model(acfg, qcfg, tp_size=args.tp if sharded else 1)

    task = make_task(acfg, args.batch, args.seq)

    key = jax.random.PRNGKey(0)
    params = model.init(key)
    labels_tree = model.labels(params)

    if args.elastic:
        if not args.ckpt_dir:
            p.error("--elastic requires --ckpt-dir")
        from repro.checkpoint import CheckpointManager
        from repro.launch import shard as S
        from repro.runtime import ElasticRunner

        n_shards = args.n_shards or args.dp
        opt = (S.zero_init_momentum(params, args.dp)
               if args.opt_shard == "zero1" else init_momentum(params))
        ckpt = CheckpointManager(args.ckpt_dir)
        runner = ElasticRunner(
            model, qcfg, labels_tree, ckpt, task.batch, dp=args.dp,
            tp=args.tp, n_shards=n_shards, opt_shard=args.opt_shard,
            lr=args.lr, wire_bits=args.wire_bits, grad_sync=args.grad_sync,
            save_every=args.save_every,
            rebalance_flags=args.rebalance_flags)
        print(f"[elastic] dp={args.dp} tp={args.tp} n_shards={n_shards} "
              f"opt={args.opt_shard} save_every={args.save_every} "
              f"resume={args.resume}")
        t0 = time.time()
        params, opt, metrics = runner.run(params, opt, args.steps,
                                          resume=args.resume)
        rep = ckpt.size_report()
        print(f"[elastic] done in {time.time() - t0:.1f}s loss "
              f"{float(metrics['loss']):.4f} restarts={runner.restarts} "
              f"reshards={len(runner.reshards)}")
        print(f"[ckpt] {rep['ckpt_bytes_q']} B packed vs "
              f"{rep['ckpt_bytes_f32_dense']} B dense-f32 "
              f"({rep['ratio']:.2f}x)")
        return

    # one jitted step fn per scheduled dr width (dr_bits is a static trace
    # constant); with no --dr-boundaries this dict holds exactly one entry
    step_fns: dict[int, object] = {}
    if sharded:
        from repro.launch import shard as S
        from repro.launch.mesh import make_cpu_mesh
        mesh = make_cpu_mesh(args.dp, args.tp)
        opt = (S.zero_init_momentum(params, args.dp)
               if args.opt_shard == "zero1" else init_momentum(params))

        def fn_for(bits):
            if bits not in step_fns:
                raw, _ = make_sharded_train_step(
                    model, qcfg, labels_tree, mesh, params, lr=args.lr,
                    dr_bits=bits, n_shards=args.n_shards or None,
                    wire_bits=args.wire_bits, grad_sync=args.grad_sync,
                    wire_codec=codec, opt_shard=args.opt_shard)
                step_fns[bits] = jax.jit(raw, donate_argnums=(0, 1))
            return step_fns[bits]

        _, specs = make_sharded_train_step(
            model, qcfg, labels_tree, mesh, params, lr=args.lr,
            n_shards=args.n_shards or None, wire_bits=args.wire_bits,
            grad_sync=args.grad_sync, wire_codec=codec,
            opt_shard=args.opt_shard)
        params = S.shard_arrays(mesh, params, specs["params"])
        opt = S.shard_arrays(mesh, opt, specs["opt"])
        print(f"[shard] mesh dp={args.dp} tp={args.tp} "
              f"n_shards={args.n_shards or args.dp} "
              f"wire={args.grad_sync}:{args.wire_bits}b "
              f"codec={codec} ({codec_why}) opt={args.opt_shard}")
    else:
        opt = init_momentum(params)

        def fn_for(bits):
            if bits not in step_fns:
                step_fns[bits] = jax.jit(
                    make_train_step(model, qcfg, labels_tree, lr=args.lr,
                                    dr_bits=bits),
                    donate_argnums=(0, 1))
            return step_fns[bits]

    ckpt = None
    start = 0
    if args.ckpt_dir:
        from repro.checkpoint import CheckpointManager
        ckpt = CheckpointManager(args.ckpt_dir)
        if args.resume and ckpt.latest_step() is not None:
            (params, opt), start, _ = ckpt.restore((params, opt))
            print(f"resumed from step {start}")

    t0 = time.time()
    cur_bits = None
    for step in range(start, args.steps):
        bits = dr_bits_schedule(step, bounds, base_bits=qcfg.k_gw)
        if bits != cur_bits:
            if bounds:
                print(f"[dr] step {step}: CQ dr width -> {bits} bits")
            cur_bits = bits
        step_fn = fn_for(bits)
        if sharded:
            from repro.launch.shard import put_batch
            batch = put_batch(mesh, task.batch(step))
        else:
            batch = jax.tree.map(jnp.asarray, task.batch(step))
        params, opt, metrics = step_fn(params, opt, batch,
                                       jnp.int32(step))
        if step % 10 == 0 or step == args.steps - 1:
            print(f"step {step:5d} loss {float(metrics['loss']):.4f} "
                  f"({time.time() - t0:.1f}s)")
        if ckpt and (step + 1) % args.save_every == 0:
            ckpt.save(step + 1, (params, opt))
    if ckpt:
        ckpt.wait()


if __name__ == "__main__":
    main()
