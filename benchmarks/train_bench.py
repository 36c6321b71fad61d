"""Training benchmark: fused-epilogue kernels vs the unfused native path,
plus the DP-scaling column for the sharded shard_map step.

Times one full fwd+bwd+update step of native-mode WAGEUBN training with the
fused dgrad/wgrad/UBN route on and off (QConfig.fuse_kernels — the two are
bit-exact, so this isolates the data-movement win of fusing Q_E2 into the
matmul prologues and the five UBN quantizers into one pass).

CSV rows (name,us_per_call,derived — `derived` is ';'-separated):
  train/<config>_fused    — us per training step; tokens/s (common.measure
                            warmup-corrected CV-guarded timing throughout)
  train/<config>_unfused  — same, fuse_kernels=False
  train/<config>_speedup  — fused-vs-unfused step-time ratio
  train/dp<N>_intwire     — sharded step @ DP=N, integer-wire grad sync
                            (the packed wire_sync_tree codec)
  train/dp<N>_f32wire     — same layout, XLA f32 all-reduce sync
  train/dp_scaling        — dp4-vs-dp1 step-time ratio (int wire)
  train/wire_codec        — dp=2 wire-bits=8: packed (tree codec,
                            two-per-int16 hops) vs unpacked (per-leaf
                            rings) step time + per-hop on-wire message
                            element counts from the traced jaxpr
  train/ckpt              — packed QTensor checkpoint: save/restore
                            latency, packed-vs-dense-f32 state bytes
                            (lossless resume format) and the int8 serving
                            export ratio (qsave.export_int8, ≥3x)

The DP rows run over a fixed n_shards=4, so every layout computes
bit-identical math — the column isolates parallel speedup + wire cost.  On
a TPU backend they run in-process on the chip's own devices (and are
printed as not measured with fewer than four); on CPU they run in a
subprocess, because virtual host devices must be configured before jax
initializes.

Scale knobs: REPRO_BENCH_FAST drops the largest config and shortens the
timed window.  On this CPU container both paths dispatch to the XLA
oracles (identical math, different fusion structure); on a TPU backend the
same toggle compares the compiled Pallas kernels.
"""
from __future__ import annotations

import os
import subprocess
import sys
import time

from .common import emit, measure


def _configs(fast: bool):
    from repro.configs.base import ArchConfig

    def lm(name, d, layers, d_ff):
        return ArchConfig(name=name, family="lm", n_layers=layers,
                          d_model=d, n_heads=max(d // 64, 2),
                          n_kv=max(d // 128, 1), d_ff=d_ff, vocab=256,
                          head_dim=64, q_chunk=64, kv_chunk=64)

    cfgs = [("lm-64", lm("bench-lm-64", 64, 2, 128), 4, 32),
            ("lm-128", lm("bench-lm-128", 128, 2, 256), 4, 64)]
    if not fast:
        cfgs.append(("lm-192", lm("bench-lm-192", 192, 3, 384), 4, 64))
    return cfgs


def _time_steps(step_fn, params, opt, batch):
    """CV-guarded step timing (common.measure): warmup absorbed outside
    the timer, samples accumulate until stable.  Returns (s, cv, n)."""
    import jax.numpy as jnp

    state = {"p": params, "o": opt, "i": 0}

    def call():
        state["i"] += 1
        state["p"], state["o"], m = step_fn(
            state["p"], state["o"], batch, jnp.int32(state["i"]))
        return m["loss"]

    return measure(call)


def main():
    import jax
    import jax.numpy as jnp

    from repro.core import preset
    from repro.data import TokenTask
    from repro.launch.train import make_train_step
    from repro.models import build_model
    from repro.optim import init_momentum

    fast = bool(os.environ.get("REPRO_BENCH_FAST"))

    for name, arch, batch_sz, seq in _configs(fast):
        task = TokenTask(vocab=arch.vocab, seq_len=seq, global_batch=batch_sz)
        batch = jax.tree.map(jnp.asarray, task.batch(0))
        tokens = batch_sz * seq
        step_us = {}
        for label, fused in (("fused", True), ("unfused", False)):
            qcfg = preset("full8", "native").replace(fuse_kernels=fused)
            model = build_model(arch, qcfg)
            params = model.init(jax.random.PRNGKey(0))
            opt = init_momentum(params)
            step_fn = jax.jit(
                make_train_step(model, qcfg, model.labels(params)))
            dt, cv, n = _time_steps(step_fn, params, opt, batch)
            step_us[label] = dt * 1e6
            emit(f"train/{name}_{label}", dt * 1e6,
                 f"tok_s={tokens / dt:.1f};steps={n};cv={cv:.3f}")
        emit(f"train/{name}_speedup", 0.0,
             f"fused_vs_unfused={step_us['unfused'] / step_us['fused']:.2f}x")
    _ckpt_bench(fast)
    _dp_scaling(fast)


def _ckpt_bench(fast: bool):
    """train/ckpt row: packed QTensor checkpoint save/restore latency and
    bytes, vs a dense-f32 write of the SAME state, plus the lossy int8
    serving-export ratio.

    The lossless resume state is floored by the 24-bit k_WU master-weight
    grid (~3 bytes/param — DESIGN.md §11), so packed-vs-f32 lands around
    1.3-1.7x; the ≥3x criterion belongs to the int8 export."""
    import shutil
    import tempfile

    import jax
    import jax.numpy as jnp

    from repro.checkpoint import CheckpointManager, qsave
    from repro.checkpoint.manager import _flatten_with_paths
    from repro.core import preset
    from repro.data import TokenTask
    from repro.launch.train import make_train_step
    from repro.models import build_model
    from repro.optim import init_momentum

    name, arch, batch_sz, seq = _configs(fast)[0]
    qcfg = preset("full8", "native")
    model = build_model(arch, qcfg)
    params = model.init(jax.random.PRNGKey(0))
    opt = init_momentum(params)
    task = TokenTask(vocab=arch.vocab, seq_len=seq, global_batch=batch_sz)
    batch = jax.tree.map(jnp.asarray, task.batch(0))
    step_fn = jax.jit(make_train_step(model, qcfg, model.labels(params)))
    # two real steps land every leaf on its WAGEUBN grid (params on the
    # 2^(1-k_WU) grid, Momentum acc on 2^(1-k_Acc)) — the state a real
    # elastic save cadence checkpoints
    for i in range(2):
        params, opt, m = step_fn(params, opt, batch, jnp.int32(i))
    jax.block_until_ready(m["loss"])
    state = {"params": params, "opt": opt}

    root = tempfile.mkdtemp(prefix="ckpt_bench_")
    try:
        packed = CheckpointManager(os.path.join(root, "q"), keep=1)
        t0 = time.perf_counter()
        packed.save(2, state, block=True)
        save_us = (time.perf_counter() - t0) * 1e6
        t0 = time.perf_counter()
        restored, _, _ = packed.restore(state, step=2)
        jax.block_until_ready(restored)
        restore_us = (time.perf_counter() - t0) * 1e6
        rep = packed.size_report(2)

        dense = CheckpointManager(os.path.join(root, "f32"), keep=1,
                                  packed=False)
        dense.save(2, state, block=True)
        dense_disk = dense.size_report(2)["disk_bytes"]

        _, fmt8 = qsave.pack_tree(
            _flatten_with_paths(qsave.export_int8(params)))
        int8_ratio = qsave.report(fmt8)["ratio"]
    finally:
        shutil.rmtree(root, ignore_errors=True)

    state_ratio = rep["ratio"]
    assert int8_ratio >= 3.0, (
        f"int8 serving export only {int8_ratio:.2f}x smaller than dense "
        f"f32 — the QTensor payload packing regressed")
    emit("train/ckpt", save_us,
         f"restore_us={restore_us:.0f};state_bytes={rep['ckpt_bytes_q']};"
         f"f32_bytes={rep['ckpt_bytes_f32_dense']};"
         f"disk_bytes={rep['disk_bytes']};dense_disk={dense_disk};"
         f"state_vs_f32={state_ratio:.2f}x;int8_vs_f32={int8_ratio:.2f}x;"
         f"arch={name}")


# --------------------------------------------------------------------------
# DP scaling (sharded shard_map step, integer wire vs f32 wire)
# --------------------------------------------------------------------------


def _dp_scaling(fast: bool):
    """DP rows: in-process on TPU devices; on CPU, spawn the DP worker
    (virtual device count must precede jax init) and re-emit its rows into
    this process's record stream."""
    import jax

    if jax.default_backend() != "cpu":
        n = len(jax.devices())
        if n < 4:
            print(f"train/dp_scaling: not measured (needs 4 "
                  f"{jax.default_backend()} devices, have {n})")
            return
        _dp_rows(emit)
        return
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_PLATFORMS="cpu")
    env.setdefault("PYTHONPATH", "src")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = subprocess.run([sys.executable, "-m", "benchmarks.train_bench",
                       "--dp-worker"], capture_output=True, text=True,
                       timeout=1800, env=env, cwd=root)
    if r.returncode != 0:
        raise RuntimeError(f"dp worker failed:\n{r.stdout[-2000:]}"
                           f"\n{r.stderr[-2000:]}")
    for line in r.stdout.splitlines():
        if line.startswith("ROW,"):
            _, name, us, derived = line.split(",", 3)
            emit(name, float(us), derived)


def _dp_rows(row):
    """Time the DP layouts; `row(name, us, derived)` records each row."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import preset
    from repro.data import TokenTask
    from repro.launch import shard as S
    from repro.launch.mesh import make_cpu_mesh
    from repro.launch.train import make_sharded_train_step
    from repro.models import build_model
    from repro.optim import init_momentum

    fast = bool(os.environ.get("REPRO_BENCH_FAST"))
    name, arch, batch_sz, seq = _configs(fast)[0]
    task = TokenTask(vocab=arch.vocab, seq_len=seq, global_batch=batch_sz)
    tokens = batch_sz * seq

    def run(dp, sync, codec="packed", wire_bits=16):
        mesh = make_cpu_mesh(dp, 1)
        qcfg = preset("full8", "native")
        model = build_model(arch, qcfg)
        params = model.init(jax.random.PRNGKey(0))
        opt = init_momentum(params)
        raw, specs = make_sharded_train_step(
            model, qcfg, model.labels(params), mesh, params,
            n_shards=4, grad_sync=sync, wire_codec=codec,
            wire_bits=wire_bits)
        step_fn = jax.jit(raw)
        params = S.shard_arrays(mesh, params, specs["params"])
        opt = S.shard_arrays(mesh, opt, specs["opt"])
        batch = S.put_batch(mesh, task.batch(0))
        return _time_steps(step_fn, params, opt, batch)

    base_us = {}
    for dp in (1, 2, 4):
        for sync, tag in (("int_ring", "intwire"), ("psum", "f32wire")):
            dt, cv, n = run(dp, sync)
            base_us[(dp, tag)] = dt * 1e6
            row(f"train/dp{dp}_{tag}", dt * 1e6,
                f"tok_s={tokens / dt:.1f};steps={n};cv={cv:.3f};"
                f"arch={name}")
    ratio = base_us[(1, 'intwire')] / base_us[(4, 'intwire')]
    wire = base_us[(4, 'f32wire')] / base_us[(4, 'intwire')]
    row("train/dp_scaling", 0.0,
        f"dp4_vs_dp1={ratio:.2f}x;f32_vs_int_at_dp4={wire:.2f}x")

    # wire-codec A/B at dp=2, wire-bits=8: packed tree codec (one ring,
    # two-per-int16 hops) vs the per-leaf unpacked rings — bit-identical
    # weights, different wires.  Message elements come from the traced
    # jaxpr (per hop: every ppermute eqn fires each of the n-1 hops).
    dt_p, _, _ = run(2, "int_ring", codec="packed", wire_bits=8)
    dt_u, _, _ = run(2, "int_ring", codec="leaf", wire_bits=8)

    def hop_elems(codec):
        mesh = make_cpu_mesh(2, 1)
        qcfg = preset("full8", "native")
        model = build_model(arch, qcfg)
        params = model.init(jax.random.PRNGKey(0))
        opt = init_momentum(params)
        raw, _ = make_sharded_train_step(
            model, qcfg, model.labels(params), mesh, params, n_shards=4,
            grad_sync="int_ring", wire_codec=codec, wire_bits=8)
        batch = jax.tree.map(jnp.asarray, task.batch(0))
        jaxpr = jax.make_jaxpr(raw)(params, opt, batch, jnp.int32(0))
        from repro.kernels.ops import collective_eqns
        pps = [c for c in collective_eqns(jaxpr.jaxpr)
               if c[0] == "ppermute"]
        return sum(int(np.prod(c[1])) for c in pps), len(pps)

    pe, pn = hop_elems("packed")
    ue, un = hop_elems("leaf")
    row("train/wire_codec", dt_p * 1e6,
        f"packed_us={dt_p * 1e6:.1f};unpacked_us={dt_u * 1e6:.1f};"
        f"packed_vs_unpacked={dt_u / dt_p:.2f}x;"
        f"hop_elems_packed={pe};hop_elems_unpacked={ue};"
        f"elem_reduction={ue / pe:.2f}x;"
        f"ppermutes_packed={pn};ppermutes_unpacked={un};"
        f"dp=2;wire_bits=8")


if __name__ == "__main__":
    if "--dp-worker" in sys.argv:
        _dp_rows(lambda name, us, derived:
                 print(f"ROW,{name},{us:.1f},{derived}"))
    else:
        main()
