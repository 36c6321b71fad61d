"""Serving example: continuous-batching int8 engine over a paged KV pool.

    PYTHONPATH=src python examples/serve_int8.py [--arch granite-3-8b]

Usage (engine path, the default):
  * builds the reduced config of the assigned arch at CPU scale and wraps
    it in `repro.serving.Engine` — a paged int8 QTensor KV-cache pool, a
    QUEUED->PREFILL->DECODE->DONE scheduler with admission control and
    recompute preemption, and one fused jit decode step over padded lanes;
  * replays staggered Poisson arrivals with mixed prompt/generation
    lengths through `run_load` (open loop, `--rate` req/s);
  * prints per-request metrics (TTFT, tokens), engine aggregates (decode
    tok/s, preemptions, stragglers) and the pool's int8-vs-fp32 byte
    report (~4x footprint ratio => ~4x more resident sequences).

Flags:
  --arch / --mode       model family + numeric mode (native: the int8 KV
                        pages feed the decode matmuls as QTensor payloads)
  --batch / --prompt-len / --gen / --rate
                        traffic shape: number of requests, prompt length
                        set base, generation length, arrival rate
  --lanes / --page-size / --max-ctx
                        engine geometry (decode batch width, KV page size)
  --legacy              the PR-1 path: one fixed batch, raw serve_step
                        loop on a contiguous int8 cache (no engine)
"""
import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs import get
from repro.core import preset
from repro.launch.cache import use_compile_cache
from repro.models import build_model
from repro.serving import Engine, greedy_token, poisson_traffic, run_load


def legacy_main(args, acfg, model, params):
    """Raw serve_step loop: batched prefill + greedy decode, no engine."""
    prompts = jax.random.randint(jax.random.PRNGKey(1),
                                 (args.batch, args.prompt_len), 0, acfg.vocab)
    t0 = time.time()
    if acfg.family == "ssm":
        cache, logits = model.prefill(params, prompts)
    else:
        cache, logits = model.prefill(params, prompts,
                                      args.prompt_len + args.gen)
    print(f"prefill {args.batch}x{args.prompt_len}: {time.time()-t0:.2f}s")

    step = jax.jit(model.serve_step)
    toks = greedy_token(logits, acfg.vocab)
    out = [toks]
    t0 = time.time()
    for _ in range(args.gen - 1):
        cache, logits = step(params, cache, toks)
        toks = greedy_token(logits, acfg.vocab)
        out.append(toks)
    dt = time.time() - t0
    gen = jnp.stack(out, axis=1)
    print(f"decoded {args.gen - 1} steps x {args.batch} seqs in {dt:.2f}s "
          f"({(args.gen - 1) * args.batch / dt:.1f} tok/s, int8 KV cache)")
    print("sample generation (token ids):", gen[0].tolist())


def engine_main(args, acfg, model, params):
    engine = Engine(model, params, max_lanes=args.lanes,
                    page_size=args.page_size, max_ctx=args.max_ctx)
    traffic = poisson_traffic(
        rate=args.rate, n_requests=args.batch,
        prompt_lens=(args.prompt_len, args.prompt_len + 8),
        gen_lens=(args.gen, max(2, args.gen // 2)), vocab=acfg.vocab)
    t0 = time.time()
    results, metrics = run_load(engine, traffic)
    wall = time.time() - t0

    for req in sorted(engine.scheduler.requests.values(),
                      key=lambda r: r.rid):
        print(f"req {req.rid}: prompt {len(req.prompt) - req.n_folded:3d} "
              f"gen {len(req.generated):3d} ttft {req.ttft * 1e3:7.1f}ms "
              f"preempts {req.preemptions}")
    print(f"served {metrics['completed']} requests in {wall:.2f}s: "
          f"{metrics['generated_tokens']} tokens, "
          f"{metrics['decode_tok_s']:.1f} decode tok/s, "
          f"{metrics['decode_steps']} fused steps, "
          f"{metrics['preemptions']} preemptions, "
          f"{metrics['straggler_steps']} stragglers")
    if "pool" in metrics:
        p = metrics["pool"]
        print(f"pool: {p['n_pages']} pages x {p['page_size']} tok, "
              f"peak {p['peak_in_use']} in use, int8 "
              f"{p['pool_bytes_int8']} B vs fp32 "
              f"{p['pool_bytes_fp32_equiv']} B "
              f"({p['footprint_ratio']:.2f}x => "
              f"{p['capacity_seqs_int8']} resident seqs vs "
              f"{p['capacity_seqs_fp32']} at the same budget)")
    sample = results[min(results)]
    print("sample generation (token ids):", sample)


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--arch", default="granite-3-8b")
    p.add_argument("--batch", type=int, default=4)
    p.add_argument("--prompt-len", type=int, default=24)
    p.add_argument("--gen", type=int, default=16)
    p.add_argument("--mode", default="native", choices=["sim", "native"],
                   help="native: the int8 KV cache is consumed as QTensors —"
                        " decode matmuls run on the cache payloads directly")
    p.add_argument("--rate", type=float, default=16.0,
                   help="Poisson arrival rate (req/s) for the engine path")
    p.add_argument("--lanes", type=int, default=4)
    p.add_argument("--page-size", type=int, default=8)
    p.add_argument("--max-ctx", type=int, default=0,
                   help="0: sized from prompt-len + gen")
    p.add_argument("--legacy", action="store_true",
                   help="raw serve_step loop instead of the engine")
    args = p.parse_args()
    use_compile_cache()
    if not args.max_ctx:
        args.max_ctx = args.prompt_len + 8 + args.gen

    acfg = get(args.arch).reduced()
    qcfg = preset("full8", args.mode)
    from repro.kernels.ops import dispatch_banner
    print(dispatch_banner(qcfg))
    model = build_model(acfg, qcfg)
    params = model.init(jax.random.PRNGKey(0))

    if args.legacy:
        legacy_main(args, acfg, model, params)
    else:
        engine_main(args, acfg, model, params)


if __name__ == "__main__":
    main()
