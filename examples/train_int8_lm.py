"""End-to-end driver: train a ~small LM for a few hundred steps under full
INT8 WAGEUBN with the whole production substrate engaged — deterministic
sharded data pipeline with background prefetch, async atomic checkpoints,
fault-tolerant runner (auto-restores on crash), straggler watchdog, and the
quantized Momentum optimizer with the dr-shrink schedule.

    PYTHONPATH=src python examples/train_int8_lm.py \
        --steps 300 --d-model 256 --layers 4 [--fail-at 120]

With --elastic the run goes through the ElasticRunner instead (DESIGN.md
§11): the sharded DP step, packed QTensor checkpoints, restore-on-failure
and bit-exact resume across DP membership changes — e.g. train under
--dp 4, kill it, then resume the SAME trajectory under --dp 2:

    PYTHONPATH=src python examples/train_int8_lm.py \
        --elastic --dp 4 --n-shards 4 --steps 300 [--fail-at 120]
    PYTHONPATH=src python examples/train_int8_lm.py \
        --elastic --dp 2 --n-shards 4 --steps 300 --resume

(The elastic path feeds batches straight from TokenTask — deterministic
in the step index, which the bit-exact-resume contract requires; the
background Prefetcher of the classic path is NOT resume-deterministic.)

At the default size this is a ~10M-parameter model; scale --d-model /
--layers / --seq up to the ~100M regime on a bigger host (the code path is
identical — the assigned full-scale configs run through the same builders).
"""
import argparse
import time

import jax
import jax.numpy as jnp

from repro.checkpoint import CheckpointManager
from repro.configs.base import ArchConfig
from repro.core import preset
from repro.core.qconfig import PRESETS
from repro.data import TokenTask
from repro.data.synthetic import Prefetcher
from repro.launch.cache import use_compile_cache
from repro.launch.train import make_train_step
from repro.models import build_model
from repro.optim import dr_bits_schedule, init_momentum, parse_boundaries
from repro.runtime import StepWatchdog, TrainRunner


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--steps", type=int, default=300)
    p.add_argument("--layers", type=int, default=4)
    p.add_argument("--d-model", type=int, default=256)
    p.add_argument("--d-ff", type=int, default=512)
    p.add_argument("--seq", type=int, default=128)
    p.add_argument("--batch", type=int, default=16)
    p.add_argument("--vocab", type=int, default=512)
    p.add_argument("--preset", default="full8",
                   choices=sorted(PRESETS))
    p.add_argument("--dr-boundaries", default="",
                   help="comma-separated steps where the CQ dr width "
                        "shrinks by one bit (paper's epoch schedule); "
                        "default: steps/2,3*steps/4")
    p.add_argument("--mode", default="sim", choices=["sim", "native"],
                   help="native: activations/weights flow as int8 QTensors "
                        "into the integer matmul kernels")
    p.add_argument("--ckpt-dir", default="/tmp/int8_lm_ckpt")
    p.add_argument("--fail-at", type=int, default=None,
                   help="inject a crash at this step (fault-tolerance demo)")
    p.add_argument("--elastic", action="store_true",
                   help="drive the run through the ElasticRunner "
                        "(sharded step + packed QTensor checkpoints + "
                        "bit-exact DP reshard)")
    p.add_argument("--dp", type=int, default=1,
                   help="elastic: data-parallel mesh size")
    p.add_argument("--n-shards", type=int, default=0,
                   help="elastic: virtual batch shards (quantization "
                        "granularity; fixed across resumes); 0 = dp")
    p.add_argument("--resume", action="store_true",
                   help="elastic: resume from the latest checkpoint in "
                        "--ckpt-dir (any dp dividing --n-shards)")
    p.add_argument("--save-every", type=int, default=50)
    args = p.parse_args()
    use_compile_cache()

    arch = ArchConfig(name="int8-lm", family="lm", n_layers=args.layers,
                      d_model=args.d_model, n_heads=args.d_model // 64 or 2,
                      n_kv=max((args.d_model // 64) // 2, 1),
                      d_ff=args.d_ff, vocab=args.vocab, head_dim=64,
                      q_chunk=128, kv_chunk=128)
    qcfg = preset(args.preset, args.mode if args.preset != "fp32" else None)
    model = build_model(arch, qcfg)
    params = model.init(jax.random.PRNGKey(0))
    n_params = sum(x.size for x in jax.tree.leaves(params))
    print(f"model: {n_params/1e6:.1f}M params, preset={args.preset}")
    from repro.kernels.ops import dispatch_banner
    print(dispatch_banner(qcfg))

    labels = model.labels(params)
    task = TokenTask(vocab=arch.vocab, seq_len=args.seq,
                     global_batch=args.batch)

    if args.elastic:
        from repro.runtime import ElasticRunner
        n_shards = args.n_shards or args.dp
        ckpt = CheckpointManager(args.ckpt_dir, keep=2)
        runner = ElasticRunner(model, qcfg, labels, ckpt, task.batch,
                               dp=args.dp, n_shards=n_shards, dr_bits=8,
                               save_every=args.save_every,
                               watchdog=StepWatchdog())
        print(f"[elastic] dp={args.dp} n_shards={n_shards} "
              f"save_every={args.save_every} resume={args.resume}")
        t0 = time.time()
        params, opt, m = runner.run(params, init_momentum(params),
                                    args.steps, resume=args.resume,
                                    fail_at=args.fail_at)
        rep = ckpt.size_report()
        print(f"done in {time.time()-t0:.1f}s; final loss "
              f"{float(m['loss']):.4f}; restarts={runner.restarts}; "
              f"reshards={len(runner.reshards)}")
        print(f"[ckpt] {rep['ckpt_bytes_q']} B packed vs "
              f"{rep['ckpt_bytes_f32_dense']} B dense-f32 "
              f"({rep['ratio']:.2f}x)")
        return

    opt = init_momentum(params)
    # dr shrinks like the paper's epoch schedule (k_gw -> k_gw-1 -> ...)
    boundaries = (parse_boundaries(args.dr_boundaries)
                  or (args.steps // 2, 3 * args.steps // 4))
    step_fns = {b: jax.jit(make_train_step(
        model, qcfg, labels,
        dr_bits=dr_bits_schedule(b, boundaries, base_bits=qcfg.k_gw)))
        for b in (0,) + boundaries}

    prefetch = Prefetcher(lambda s: task.batch(s), depth=2)
    ckpt = CheckpointManager(args.ckpt_dir, keep=2)

    def one_step(state, step):
        params, opt = state
        _, host_batch = prefetch.get()
        batch = jax.tree.map(jnp.asarray, host_batch)
        fn = step_fns[max(b for b in step_fns if b <= step)]
        params, opt, m = fn(params, opt, batch, jnp.int32(step))
        if step % 20 == 0:
            print(f"  step {step:4d} loss {float(m['loss']):.4f}")
        return (params, opt), m

    runner = TrainRunner(one_step, ckpt, save_every=50,
                         watchdog=StepWatchdog())
    t0 = time.time()
    (params, opt), m = runner.run((params, opt), args.steps,
                                  fail_at=args.fail_at)
    prefetch.close()
    print(f"done in {time.time()-t0:.1f}s; final loss "
          f"{float(m['loss']):.4f}; restarts={runner.restarts}; "
          f"stragglers flagged={len(runner.watchdog.flags)}")


if __name__ == "__main__":
    main()
