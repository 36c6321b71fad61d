"""Shared benchmark helpers: small-scale training harnesses + CSV output.

The paper's experiments are ResNet/ImageNet-scale; this container is one
CPU core, so every accuracy benchmark runs the same *protocol* at reduced
scale (reduced ResNet on a learnable synthetic image task / tiny LM on the
arithmetic token task).  Scale knobs: REPRO_BENCH_STEPS / REPRO_BENCH_FAST.
"""
from __future__ import annotations

import dataclasses
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.core import preset
from repro.core.qconfig import QConfig
from repro.data import ImageTask, TokenTask, resolve_image_task
from repro.launch.train import make_sharded_train_step, make_train_step
from repro.models import build_model
from repro.optim import dr_bits_schedule, init_momentum


def steps_default(n: int) -> int:
    if os.environ.get("REPRO_BENCH_FAST"):
        return max(8, n // 8)
    return int(os.environ.get("REPRO_BENCH_STEPS", n))


RESNET_BENCH = ArchConfig(name="resnet-bench", family="resnet",
                          block="basic", stage_sizes=(1, 1),
                          num_classes=8, img_size=16)

LM_BENCH = ArchConfig(name="lm-bench", family="lm", n_layers=2, d_model=64,
                      n_heads=4, n_kv=2, d_ff=128, vocab=64, head_dim=16,
                      q_chunk=32, kv_chunk=32)


def image_task(batch: int = 64, seed: int = 1):
    """Benchmark image source: the real npz pipeline when REPRO_DATA_DIR is
    set (synthetic fallback behind REPRO_SYNTHETIC_DATA=1), the synthetic
    blob task otherwise.  Returns (task, tag) — stamp `data=tag` into rows.
    """
    return resolve_image_task(
        batch, synthetic=bool(os.environ.get("REPRO_SYNTHETIC_DATA")),
        img_size=RESNET_BENCH.img_size,
        num_classes=RESNET_BENCH.num_classes, seed=seed)


def resnet_arch_for(task) -> ArchConfig:
    """RESNET_BENCH re-shaped to the task's geometry (real datasets may
    differ from the 16px/8-class synthetic default)."""
    return dataclasses.replace(RESNET_BENCH, num_classes=task.num_classes,
                               img_size=task.img_size)


def train_resnet(qcfg: QConfig, steps: int, batch: int = 64, lr: float = 0.05,
                 seed: int = 0, eval_batches: int = 4, task=None,
                 dr_boundaries: tuple = ()):
    task, tag = (task, "caller") if task is not None else image_task(batch)
    model = build_model(resnet_arch_for(task), qcfg)
    params = model.init(jax.random.PRNGKey(seed))
    opt = init_momentum(params)
    labels = model.labels(params)
    # one jitted step per scheduled CQ dr width (static trace constant)
    step_fns = {}

    def fn_for(bits):
        if bits not in step_fns:
            step_fns[bits] = jax.jit(
                make_train_step(model, qcfg, labels, lr=lr, dr_bits=bits))
        return step_fns[bits]

    losses = []
    t0 = time.time()
    for s in range(steps):
        b = jax.tree.map(jnp.asarray, task.batch(s))
        fn = fn_for(dr_bits_schedule(s, dr_boundaries, base_bits=qcfg.k_gw))
        params, opt, m = fn(params, opt, b, jnp.int32(s))
        losses.append(float(m["loss"]))
    # held-out accuracy (val split / fresh synthetic steps)
    accs = []
    fwd = jax.jit(lambda p, b: model.loss(p, b)[1]["acc"])
    for i in range(eval_batches):
        b = jax.tree.map(jnp.asarray, task.holdout_batch(i))
        accs.append(float(fwd(params, b)))
    return {"losses": losses, "acc": float(np.mean(accs)),
            "wall_s": time.time() - t0, "params": params, "model": model,
            "data": tag, "task": task}


def train_resnet_sharded(qcfg: QConfig, steps: int, *, wire_bits: int,
                         n_shards: int = 2, batch: int = 64,
                         lr: float = 0.05, seed: int = 0,
                         eval_batches: int = 4, task=None):
    """train_resnet through the sharded step on a dp=1 mesh: the integer
    wire's quantization numerics (per-virtual-shard rounding against the
    pmax'ed scale at `wire_bits`, staged widening for sub-8 fan-ins) are
    fully engaged without needing multiple devices — the wire-bits
    sensitivity axis of table2."""
    from repro.launch import shard as S
    from repro.launch.mesh import make_cpu_mesh
    from repro.launch.shard import put_batch

    task, tag = (task, "caller") if task is not None else image_task(batch)
    model = build_model(resnet_arch_for(task), qcfg)
    params = model.init(jax.random.PRNGKey(seed))
    opt = init_momentum(params)
    labels = model.labels(params)
    mesh = make_cpu_mesh(1, 1)
    raw, specs = make_sharded_train_step(
        model, qcfg, labels, mesh, params, lr=lr, n_shards=n_shards,
        wire_bits=wire_bits, wire_codec="auto")
    step_fn = jax.jit(raw)
    params = S.shard_arrays(mesh, params, specs["params"])
    opt = S.shard_arrays(mesh, opt, specs["opt"])
    losses = []
    t0 = time.time()
    for s in range(steps):
        b = put_batch(mesh, task.batch(s))
        params, opt, m = step_fn(params, opt, b, jnp.int32(s))
        losses.append(float(m["loss"]))
    accs = []
    fwd = jax.jit(lambda p, b: model.loss(p, b)[1]["acc"])
    for i in range(eval_batches):
        b = jax.tree.map(jnp.asarray, task.holdout_batch(i))
        accs.append(float(fwd(params, b)))
    return {"losses": losses, "acc": float(np.mean(accs)),
            "wall_s": time.time() - t0, "params": params, "model": model,
            "data": tag, "task": task}


def train_lm(qcfg: QConfig, steps: int, batch: int = 8, seq: int = 32,
             lr: float = 0.05, seed: int = 0):
    model = build_model(LM_BENCH, qcfg)
    params = model.init(jax.random.PRNGKey(seed))
    opt = init_momentum(params)
    labels = model.labels(params)
    step_fn = jax.jit(make_train_step(model, qcfg, labels, lr=lr))
    task = TokenTask(vocab=LM_BENCH.vocab, seq_len=seq, global_batch=batch)
    losses = []
    t0 = time.time()
    for s in range(steps):
        b = jax.tree.map(jnp.asarray, task.batch(s))
        params, opt, m = step_fn(params, opt, b, jnp.int32(s))
        losses.append(float(m["loss"]))
    return {"losses": losses, "final_loss": float(np.mean(losses[-5:])),
            "wall_s": time.time() - t0, "params": params, "model": model}


def measure(call, *, warmup: int = 2, min_steps: int | None = None,
            max_steps: int | None = None, target_cv: float = 0.10):
    """Warmup-corrected, CV-guarded wall-clock of a nullary `call`.

    `warmup` untimed calls absorb compile + first-dispatch cost (the old
    steps=2-3 timings charged them to the measurement, which is why
    fused-vs-unfused ratios oscillated 0.80x-1.11x between commits).  Then
    timed calls accumulate until the coefficient of variation of the
    per-call samples drops under `target_cv` — or `max_steps` caps the
    spend (REPRO_BENCH_FAST shrinks both bounds).  The mean discards the
    single slowest sample once there are enough (one GC pause or page-in
    shouldn't own the number).

    Returns (mean_s, cv, n_samples).
    """
    fast = bool(os.environ.get("REPRO_BENCH_FAST"))
    if min_steps is None:
        min_steps = 3 if fast else 6
    if max_steps is None:
        max_steps = 8 if fast else 32
    for _ in range(max(warmup, 1)):
        jax.block_until_ready(call())
    ts: list[float] = []
    while True:
        t0 = time.perf_counter()
        jax.block_until_ready(call())
        ts.append(time.perf_counter() - t0)
        if len(ts) < min_steps:
            continue
        kept = sorted(ts)[:-1] if len(ts) >= 5 else ts
        mu = float(np.mean(kept))
        cv = float(np.std(kept) / mu) if mu > 0 else 0.0
        if cv <= target_cv or len(ts) >= max_steps:
            return mu, cv, len(ts)


# rows emitted since the last take_records() — benchmarks.run snapshots
# these into the append-style BENCH_<suite>.json trajectory files
RECORDS: list[dict] = []


def emit(name: str, us_per_call: float, derived: str):
    """The harness CSV contract: name,us_per_call,derived."""
    RECORDS.append({"name": name, "us_per_call": round(us_per_call, 1),
                    "derived": derived})
    print(f"{name},{us_per_call:.1f},{derived}")


def take_records() -> list[dict]:
    """Drain the emitted-row buffer (one suite's worth when called by the
    benchmarks.run harness between suites)."""
    out, RECORDS[:] = list(RECORDS), []
    return out
