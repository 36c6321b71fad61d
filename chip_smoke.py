"""Chip smoke test: the paper's ResNet-50 full-int8 training step on a TPU.

Run from the repository root on a machine with a TPU:

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # the DP integer wire on four chips

One chip: trains ResNet-50 at its published widths (bottleneck 3/4/6/3,
224 px, 1000 classes) at batch 32 for 3 steps under `--preset full8 --mode
native`, built with launch/train.py's own builders, so the compiled Pallas
kernels are the route.  It prints the dispatch banner, compile seconds,
per-step loss and step seconds, peak device bytes and the number of
`tpu_custom_call`s in the compiled step, and fails on a non-finite loss or
a step without kernels.  For correctness it compares the init-params
forward loss on the first 8 images of the step-0 batch, computed on the
chip through the kernels, with the same loss computed by a CPU-only child
process through the jnp oracles, and requires agreement within 1%.

Four chips (`--four-chips`, this phase only): two steps of the sharded
step (`make_sharded_train_step`) at dp=4, n_shards=4 over the integer wire,
against the same step at dp=1, n_shards=4 on device 0; params and momentum
must be bitwise equal (the dp=1 == dp=N contract of DESIGN.md §9).

Without a TPU it exits non-zero and prints no result.  The last line of
stdout is the result: {"ok": true, "device": {...}}.
"""
from __future__ import annotations

import argparse
import concurrent.futures
import json
import math
import os
import subprocess
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(ROOT, "src")

ARCH = "resnet50"
BATCH = 32
STEPS = 3
FOUR_CHIP_STEPS = 2
REF_IMAGES = 8
REF_RTOL = 0.01


def _setup():
    """Model, synthetic task and init params, exactly as `python -m
    repro.launch.train --arch resnet50 --preset full8 --mode native
    --batch 32` builds them."""
    import jax

    from repro.configs import get as get_arch
    from repro.core.qconfig import preset
    from repro.launch.train import make_task
    from repro.models import build_model

    acfg = get_arch(ARCH)
    qcfg = preset("full8", "native")
    model = build_model(acfg, qcfg)
    task = make_task(acfg, BATCH, 0)
    params = model.init(jax.random.PRNGKey(0))
    return qcfg, model, task, params


def _ref_batch(task) -> dict:
    return {k: v[:REF_IMAGES] for k, v in task.batch(0).items()}


def _forward_loss(model, params, batch) -> float:
    import jax
    return float(jax.jit(lambda p, b: model.loss(p, b)[0])(params, batch))


def cpu_reference() -> None:
    """Child process body (JAX_PLATFORMS=cpu): the init forward loss
    through the oracle route, printed as the last stdout line."""
    _, model, task, params = _setup()
    loss = _forward_loss(model, params, _ref_batch(task))
    print(json.dumps({"cpu_loss": loss}))


def _fail(msg: str) -> None:
    print(f"[smoke] FAIL: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def _say(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def _custom_calls(compiled) -> int:
    return compiled.as_text().count('custom_call_target="tpu_custom_call"')


def _peak_bytes(dev) -> int | None:
    stats = dev.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def _compile_in_background(pool, fn, *args):
    """Start lowering + compiling fn(*args) on a worker thread (XLA
    compiles outside the GIL, so programs compile side by side); the
    future yields (compiled, compile seconds)."""
    def work():
        t0 = time.perf_counter()
        return fn.lower(*args).compile(), time.perf_counter() - t0
    return pool.submit(work)


def one_chip(child) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops
    from repro.launch.train import make_train_step
    from repro.optim import init_momentum

    qcfg, model, task, params = _setup()
    n_params = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))
    _say(f"model {ARCH}: {n_params} params, img {task.img_size} px, "
         f"{task.num_classes} classes, batch {BATCH}, full8/native")
    _say(ops.dispatch_banner(qcfg))

    labels = model.labels(params)
    opt = init_momentum(params)
    step_fn = jax.jit(make_train_step(model, qcfg, labels),
                      donate_argnums=(0, 1))
    batch = jax.tree.map(jnp.asarray, task.batch(0))
    with concurrent.futures.ThreadPoolExecutor(1) as pool:
        pending = _compile_in_background(pool, step_fn, params, opt, batch,
                                         jnp.int32(0))
        # the init forward runs while the step compiles (and before the
        # step donates the init params)
        chip_loss = _forward_loss(model, params, _ref_batch(task))
        compiled, compile_s = pending.result()
    _say(f"compile_s {compile_s:.3f}")
    n_kernels = _custom_calls(compiled)
    _say(f"tpu_custom_call count {n_kernels}")
    if n_kernels == 0:
        _fail("the compiled step holds no Pallas kernel")
    mem = compiled.memory_analysis()
    if mem is not None:
        _say(f"memory_analysis args {mem.argument_size_in_bytes} B, temp "
             f"{mem.temp_size_in_bytes} B, out {mem.output_size_in_bytes} B")

    for s in range(STEPS):
        if s:
            batch = jax.tree.map(jnp.asarray, task.batch(s))
        t0 = time.perf_counter()
        params, opt, metrics = compiled(params, opt, batch, jnp.int32(s))
        loss = float(jax.block_until_ready(metrics["loss"]))
        _say(f"step {s} loss {loss:.6f} step_s "
             f"{time.perf_counter() - t0:.3f}")
        if not math.isfinite(loss):
            _fail(f"non-finite loss at step {s}")
    _say(f"peak_bytes_in_use {_peak_bytes(jax.devices()[0])}")
    rep = ops.dispatch_report(qcfg)
    _say(f"oracle calls on tpu {rep['oracle_on_tpu']}")

    proc, log = child
    if proc.wait(timeout=600) != 0:
        log.seek(0)
        _fail(f"cpu reference exited {proc.returncode}:\n"
              f"{log.read().decode()[-3000:]}")
    log.seek(0)
    cpu_loss = json.loads(log.read().decode().strip().splitlines()[-1])[
        "cpu_loss"]
    gap = abs(chip_loss - cpu_loss) / abs(cpu_loss)
    _say(f"init forward loss on {REF_IMAGES} images: chip {chip_loss:.6f} "
         f"cpu {cpu_loss:.6f} rel_gap {gap:.3e} (limit {REF_RTOL})")
    if not gap <= REF_RTOL:
        _fail("chip and cpu init losses disagree")


def four_chips() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.launch import shard as S
    from repro.launch.mesh import make_cpu_mesh
    from repro.launch.train import make_sharded_train_step
    from repro.optim import init_momentum
    from repro.runtime.compress import default_wire_codec

    if len(jax.devices()) < 4:
        _fail(f"--four-chips needs 4 devices, have {len(jax.devices())}")
    qcfg, model, task, params = _setup()
    labels = model.labels(params)
    codec, why = default_wire_codec()
    _say(f"sharded {ARCH} batch {BATCH} n_shards 4 codec {codec} ({why})")

    layouts = {}
    with concurrent.futures.ThreadPoolExecutor(2) as pool:
        for dp in (1, 4):
            mesh = make_cpu_mesh(dp, 1)
            raw, specs = make_sharded_train_step(
                model, qcfg, labels, mesh, params, n_shards=4,
                wire_codec="auto")
            p = S.shard_arrays(mesh, params, specs["params"])
            o = S.shard_arrays(mesh, init_momentum(params), specs["opt"])
            batch = S.put_batch(mesh, task.batch(0))
            layouts[dp] = (mesh, p, o, batch, _compile_in_background(
                pool, jax.jit(raw), p, o, batch, jnp.int32(0)))
        layouts = {dp: v[:4] + v[4].result() for dp, v in layouts.items()}

    final = {}
    for dp, (mesh, p, o, batch, compiled, compile_s) in layouts.items():
        _say(f"dp={dp} compile_s {compile_s:.3f} "
             f"tpu_custom_call count {_custom_calls(compiled)}")
        for s in range(FOUR_CHIP_STEPS):
            if s:
                batch = S.put_batch(mesh, task.batch(s))
            t0 = time.perf_counter()
            p, o, metrics = compiled(p, o, batch, jnp.int32(s))
            loss = float(jax.block_until_ready(metrics["loss"]))
            _say(f"dp={dp} step {s} loss {loss:.6f} step_s "
                 f"{time.perf_counter() - t0:.3f}")
            if not math.isfinite(loss):
                _fail(f"non-finite loss at dp={dp} step {s}")
        final[dp] = jax.device_get((p, o))
    _say(f"peak_bytes_in_use per device "
         f"{[_peak_bytes(d) for d in jax.devices()[:4]]}")

    a, b = (jax.tree.leaves(final[dp]) for dp in (1, 4))
    differ = sum(not np.array_equal(x, y) for x, y in zip(a, b))
    _say(f"dp=4 vs dp=1 params+momentum: {len(a)} leaves, {differ} differ")
    if len(a) != len(b) or differ:
        _fail("dp=4 is not bitwise equal to dp=1 at n_shards=4")


def main() -> None:
    p = argparse.ArgumentParser("chip_smoke")
    p.add_argument("--four-chips", action="store_true",
                   help="run only the dp=4 integer-wire phase and its dp=1 "
                        "comparison (needs four chips)")
    args = p.parse_args()
    if not os.path.isdir(os.path.join(SRC, "repro")):
        _fail(f"no repro package under {SRC}")
    sys.path.insert(0, SRC)

    child = None
    if not args.four_chips:
        # the CPU reference starts before this process touches JAX, so it
        # never competes for the chip; its output goes to a file, so a
        # chatty child can never block on a full pipe
        env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=SRC)
        log = tempfile.TemporaryFile()
        child = (subprocess.Popen(
            [sys.executable, "-c",
             "import chip_smoke; chip_smoke.cpu_reference()"],
            cwd=ROOT, env=env, stdout=log, stderr=subprocess.STDOUT), log)
    try:
        import jax

        from repro.launch.cache import use_compile_cache

        dev = jax.devices()[0]
        if dev.platform != "tpu":
            _fail(f"no TPU: jax found {dev.platform} devices")
        _say(f"compile cache {use_compile_cache()}")
        _say(f"device {dev.device_kind} x{len(jax.devices())}")
        if args.four_chips:
            four_chips()
        else:
            one_chip(child)
        print(json.dumps({"ok": True, "device": {
            "platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}}))
    finally:
        if child is not None and child[0].poll() is None:
            child[0].kill()
            child[0].wait()


if __name__ == "__main__":
    main()
