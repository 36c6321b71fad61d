import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# (the two lines above MUST run before any jax import — device count locks
# at first init.  REPRO_DEVICES overrides for CI-scale smoke runs.)
if os.environ.get("REPRO_DEVICES"):
    os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count="
                               + os.environ["REPRO_DEVICES"])

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

For each cell we build the production mesh, attach NamedShardings to
ShapeDtypeStruct stand-ins for every input (weights, optimizer state, batch
or cache — no device allocation anywhere), lower the jitted step, compile,
and record memory_analysis / cost_analysis / the collective schedule into a
JSON artifact that §Roofline and §Perf read.

    PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both
    PYTHONPATH=src python -m repro.launch.dryrun --arch granite-34b \
        --shape train_4k --mesh multi
"""

import argparse
import json
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ASSIGNED, get as get_arch
from repro.configs.base import LM_SHAPES
from repro.core.qconfig import preset
from repro.launch.mesh import auto_mesh, make_production_mesh, mesh_axes
from repro.launch.roofline import parse_collectives
from repro.launch.train import make_prefill, make_serve_step, make_train_step
from repro.models import build_model
from repro.optim import init_momentum


def _tiny() -> bool:
    return bool(os.environ.get("REPRO_DEVICES"))


def make_mesh(multi_pod: bool):
    if _tiny():
        shape = (2, 2, 2) if multi_pod else (2, 2)
        axes = ("pod", "data", "model") if multi_pod else ("data", "model")
        return auto_mesh(shape, axes)
    return make_production_mesh(multi_pod=multi_pod)


def _shard_sds(tree, pspec_tree, mesh):
    def f(sds, spec):
        return jax.ShapeDtypeStruct(sds.shape, sds.dtype,
                                    sharding=NamedSharding(mesh, spec))
    return jax.tree.map(f, tree, pspec_tree)


def _count_params(params_sds, acfg):
    total = active = 0
    flat, _ = jax.tree_util.tree_flatten_with_path(params_sds)
    for path, leaf in flat:
        keys = [str(getattr(p, "key", "")) for p in path]
        n = 1
        for d in leaf.shape:
            n *= d
        total += n
        if "embed" in keys:
            continue
        if acfg.moe_experts and any(k in ("wg", "wu", "wd") for k in keys) \
                and "moe" in keys:
            active += n * acfg.moe_topk / acfg.moe_experts
        else:
            active += n
    return total, active


def _model_flops(acfg, kind, shape_name, n_active):
    s, b, _ = LM_SHAPES[shape_name]
    if acfg.family == "encdec":
        tokens = b * (s + s // acfg.tgt_ratio)
    else:
        tokens = b * s
    if kind == "train":
        return 6.0 * n_active * tokens
    if kind == "prefill":
        return 2.0 * n_active * tokens
    return 2.0 * n_active * b       # decode: one token per sequence


def _compile_cell(acfg, shape, mesh, dp, tp, qcfg, sb, n_micro=1):
    """Lower + compile one configuration; returns (compiled, t_lower,
    t_compile)."""
    model = build_model(acfg, qcfg, mesh=mesh, dp_axes=dp, tp_axis=tp)
    specs, kind = model.input_specs(shape, sb=sb)
    params_sds = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    params_sh = _shard_sds(params_sds, model.pspecs(), mesh)
    dp_size = 1
    for a in dp:
        dp_size *= mesh.shape[a]

    if kind == "train":
        labels_tree = model.labels(params_sds)
        opt_sds = jax.eval_shape(init_momentum, params_sds)
        opt_sh = _shard_sds(
            opt_sds, type(opt_sds)(acc=model.pspecs(), step=P()), mesh)
        batch_sh = _shard_sds(specs, model.batch_pspec(), mesh)
        fn = make_train_step(model, qcfg, labels_tree, n_micro=n_micro)
        args = (params_sh, opt_sh, batch_sh,
                jax.ShapeDtypeStruct((), jnp.int32))
        jfn = jax.jit(fn, donate_argnums=(0, 1))
    elif kind == "prefill":
        fn = make_prefill(model, shape)
        bspec = model.batch_pspec()
        if acfg.family == "encdec":
            in_sh = _shard_sds(specs["frames"], bspec["frames"], mesh)
        else:
            in_sh = _shard_sds(specs["tokens"], bspec["tokens"], mesh)
        args = (params_sh, in_sh)
        cache_ps = model.cache_pspec(long=False)
        cache_out = jax.tree.map(
            lambda s: NamedSharding(mesh, s), cache_ps)
        if acfg.family == "encdec":
            out_sh = cache_out
        else:
            out_sh = (cache_out, NamedSharding(mesh, P(dp, None)))
        jfn = jax.jit(fn, out_shardings=out_sh)
    else:  # decode
        long = shape.startswith("long")
        cache_sh = _shard_sds(specs["cache"], model.cache_pspec(long=long),
                              mesh)
        tok_spec = P(dp) if specs["tokens"].shape[0] % dp_size == 0 else P()
        tok_sh = _shard_sds(specs["tokens"], tok_spec, mesh)
        fn = make_serve_step(model)
        args = (params_sh, cache_sh, tok_sh)
        jfn = jax.jit(fn, donate_argnums=(1,))

    t0 = time.time()
    lowered = jfn.lower(*args)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    return compiled, kind, params_sds, t_lower, time.time() - t0


def _depth_points(acfg):
    """Two depth settings + extrapolation step count for affine cost fits.

    metric(full) = metric(A) + (metric(B) - metric(A)) * steps
    """
    if acfg.family == "hybrid":
        ae = acfg.attn_every
        gfull = acfg.n_layers // ae
        tail = acfg.n_layers - gfull * ae
        return (acfg.replace(n_layers=ae + tail),
                acfg.replace(n_layers=2 * ae + tail),
                float(gfull - 1))
    if acfg.family == "encdec":
        return (acfg.replace(enc_layers=2, dec_layers=2),
                acfg.replace(enc_layers=4, dec_layers=4),
                (acfg.enc_layers - 2) / 2.0)
    la = min(2, acfg.n_layers)
    lb = min(4, acfg.n_layers)
    steps = (acfg.n_layers - la) / max(lb - la, 1)
    return acfg.replace(n_layers=la), acfg.replace(n_layers=lb), steps


def _cost_metrics(compiled):
    ca = compiled.cost_analysis() or {}
    colls = parse_collectives(compiled.as_text())
    return {
        "flops": float(ca.get("flops", 0.0)),
        "bytes": float(ca.get("bytes accessed", 0.0)),
        "coll": float(sum(v["bytes"] for v in colls.values())),
        "coll_wire": float(sum(v["wire_bytes"] for v in colls.values())),
    }, colls


def _parse_overrides(pairs):
    out = {}
    for kv in pairs or []:
        k, v = kv.split("=", 1)
        if v in ("true", "True"):
            v = True
        elif v in ("false", "False"):
            v = False
        else:
            try:
                v = int(v)
            except ValueError:
                try:
                    v = float(v)
                except ValueError:
                    pass
        out[k] = v
    return out


def run_cell(arch: str, shape: str, multi_pod: bool, qpreset: str = "full8",
             mode: str = "native", q_over=None, a_over=None) -> dict:
    acfg = get_arch(arch)
    if _tiny():
        acfg = acfg.reduced()
    if a_over:
        acfg = acfg.replace(**a_over)
    mesh = make_mesh(multi_pod)
    dp, tp = mesh_axes(mesh)
    qcfg = preset(qpreset, mode)
    if q_over:
        qcfg = qcfg.replace(**q_over)
    sb = (64, 8) if _tiny() else None
    s, b, _ = LM_SHAPES[shape]
    if _tiny():
        s, b = sb

    # 1) FULL compile: the pass/fail gate + memory analysis.
    # Train cells use microbatched grad accumulation (one sequence per
    # device per microbatch) — the production memory policy; cost compiles
    # below stay n_micro=1 (same total work, exact loop-free accounting).
    dp_size = 1
    for ax in dp:
        dp_size *= mesh.shape[ax]
    n_micro = 1
    if LM_SHAPES[shape][2] == "train" and not _tiny():
        n_micro = max(1, b // dp_size)
    compiled, kind, params_sds, t_lower, t_compile = _compile_cell(
        acfg, shape, mesh, dp, tp, qcfg, sb, n_micro=n_micro)
    ma = compiled.memory_analysis()
    raw, colls = _cost_metrics(compiled)

    # 2) two depth-point cost compiles with single-trip inner loops
    #    (XLA cost analysis counts while bodies ONCE; unchunked attention /
    #    scan makes inner loops trip-1 = exact, and depth is extrapolated
    #    affinely — see EXPERIMENTS.md §Dry-run "cost accounting").
    #    The roofline table is single-pod only (per assignment), so
    #    multi-pod cells skip the cost compiles — their FULL compile above
    #    is the multi-pod deliverable (the pod axis shards, memory fits).
    steps = 0.0
    if multi_pod:
        cost = dict(raw)
    else:
        st = s if acfg.family != "encdec" else max(s, s // acfg.tgt_ratio)
        unchunked = dict(q_chunk=st, kv_chunk=st, unroll_layers=True)
        if acfg.family == "hybrid":
            # SSD intra-chunk: single-chunk variants stall constant folding
            # and fully unrolled chunk scans blow up XLA optimization time;
            # keep the chunk scan rolled (bodies counted once).  The SSD
            # intra-chunk share of zamba2 FLOPs is small vs projections +
            # shared attention, so this is a documented <~20% undercount on
            # that component only (cost_note in the artifact).
            unchunked.update(scan_chunk=acfg.scan_chunk)
        else:
            # mamba1 uses associative_scan (loop-free: exact at any chunk)
            unchunked.update(scan_chunk=st)
        acfg_a, acfg_b, steps = _depth_points(acfg.replace(**unchunked))
        comp_a, _, _, _, _ = _compile_cell(acfg_a, shape, mesh, dp, tp, qcfg,
                                           sb)
        ca_a, _ = _cost_metrics(comp_a)
        if steps > 0:
            comp_b, _, _, _, _ = _compile_cell(acfg_b, shape, mesh, dp, tp,
                                               qcfg, sb)
            ca_b, _ = _cost_metrics(comp_b)
        else:
            ca_b = ca_a
        cost = {k: ca_a[k] + (ca_b[k] - ca_a[k]) * steps for k in ca_a}

    n_total, n_active = _count_params(params_sds, acfg)
    art = {
        "arch": arch, "shape": shape, "n_micro": n_micro,
        "q_overrides": q_over or {}, "a_overrides": a_over or {},
        "mesh": "multi" if multi_pod else "single",
        "kind": kind, "devices": mesh.devices.size,
        "preset": qpreset, "qmode": mode,
        "lower_s": t_lower, "compile_s": t_compile,
        "flops_per_device": cost["flops"],
        "bytes_per_device": cost["bytes"],
        "collective_bytes_per_device": cost["coll"],
        "collective_wire_bytes_per_device": cost["coll_wire"],
        "raw_once_through": raw,
        "depth_extrapolation_steps": steps,
        "cost_note": ("hybrid: SSD chunk-scan bodies counted once "
                      "(<~20% undercount on the intra-chunk component)"
                      if acfg.family == "hybrid" and not multi_pod else ""),
        "collectives": colls,
        "mem_analysis": {
            "arg_bytes": int(ma.argument_size_in_bytes),
            "out_bytes": int(ma.output_size_in_bytes),
            "temp_bytes": int(ma.temp_size_in_bytes),
            "alias_bytes": int(ma.alias_size_in_bytes),
            "peak_bytes_est": int(ma.argument_size_in_bytes
                                  + ma.output_size_in_bytes
                                  + ma.temp_size_in_bytes
                                  - ma.alias_size_in_bytes),
        } if ma else {},
        "n_params": n_total, "n_params_active": n_active,
        "model_flops_global": _model_flops(acfg, kind, shape, n_active),
    }
    return art


def cells_for(arch: str):
    return get_arch(arch).shapes


def main(argv=None):
    p = argparse.ArgumentParser("repro.launch.dryrun")
    p.add_argument("--arch", default=None)
    p.add_argument("--shape", default=None)
    p.add_argument("--mesh", default="both",
                   choices=["single", "multi", "both"])
    p.add_argument("--all", action="store_true")
    p.add_argument("--preset", default="full8")
    p.add_argument("--qmode", default="native")
    p.add_argument("--out-dir", default="artifacts/dryrun")
    p.add_argument("--force", action="store_true")
    p.add_argument("--tag", default="")
    p.add_argument("--set-q", action="append", default=[],
                   help="QConfig override key=val (repeatable), e.g. "
                        "--set-q tp_comm_dtype=bf16")
    p.add_argument("--set-arch", action="append", default=[],
                   help="ArchConfig override key=val, e.g. --set-arch "
                        "remat=none")
    args = p.parse_args(argv)
    q_over = _parse_overrides(args.set_q)
    a_over = _parse_overrides(args.set_arch)

    os.makedirs(args.out_dir, exist_ok=True)
    archs = ASSIGNED if (args.all or not args.arch) else [args.arch]
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    failures = []
    for arch in archs:
        shapes = [args.shape] if args.shape else cells_for(arch)
        for shape in shapes:
            for mp in meshes:
                name = f"{arch}_{shape}_{'multi' if mp else 'single'}"
                if args.tag:
                    name += f"_{args.tag}"
                out = os.path.join(args.out_dir, name + ".json")
                if os.path.exists(out) and not args.force:
                    print(f"[skip] {name} (exists)")
                    continue
                print(f"[cell] {name} ...", flush=True)
                try:
                    art = run_cell(arch, shape, mp, args.preset,
                                                   args.qmode, q_over, a_over)
                    with open(out, "w") as f:
                        json.dump(art, f, indent=1)
                    print(f"  ok: compile {art['compile_s']:.1f}s, "
                          f"flops/dev {art['flops_per_device']:.3e}, "
                          f"coll/dev {art['collective_bytes_per_device']:.3e}B",
                          flush=True)
                    if art["mem_analysis"]:
                        print(f"  mem/dev: "
                              f"{art['mem_analysis']['peak_bytes_est']/2**30:.2f}"
                              " GiB (args+temp+out)", flush=True)
                except Exception as e:  # noqa: BLE001
                    failures.append((name, repr(e)))
                    print(f"  FAIL: {e}")
                    traceback.print_exc()
    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for n, e in failures:
            print(" ", n, e)
        raise SystemExit(1)
    print("\nall requested dry-run cells compiled OK")


if __name__ == "__main__":
    main()
