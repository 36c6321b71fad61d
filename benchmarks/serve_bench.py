"""Serving benchmark: continuous-batching engine under open-loop Poisson
traffic at several arrival rates, vs the sequential naive baseline, plus
the fused-vs-unfused decode comparison (mirroring train_bench's fused
column: QConfig.fuse_kernels toggles the paged-attention route, bit-exact
either way, so the delta isolates the page-gather traffic the fused kernel
removes).

CSV rows (name,us_per_call,derived — `derived` is ';'-separated):
  serve/rate<r>         — us per fused decode step; decode tok/s, mean/max
                          + p50/p99 TTFT, p50/p99 TPOT, preemptions under
                          rate r req/s
  serve/rate<r>_chunked — same load through the chunked-prefill engine
                          (one jit-stable prefill trace for every prompt
                          length instead of a compile per length — the
                          TTFT lever)
  serve/ttft_breakdown  — TTFT split queue_ms vs prefill_ms at the middle
                          rate, one row per prefill mode (both polarities:
                          mode=monolithic and mode=chunked; CI greps both)
  serve/prefix_hit      — radix-cache sweep over sharing {0, 0.5, 0.9}:
                          hit_rate, tok/s, mean TTFT per sharing level
                          (CI greps the sharing=0 and sharing=0.9 rows)
  serve/sharded         — one row per (tp, dp) layout at the middle rate:
                          aggregate decode tok/s + p50/p99 TTFT and TPOT
                          through the shard_map'd engine (tp=2) and the
                          replica Router (dp=2); device-gated, so the
                          multi-device CI lane greps both tp polarities
  serve/naive           — us per decode step of one-request-at-a-time serving
  serve/speedup         — engine-vs-naive aggregate decode tok/s ratio
  serve/pool            — int8-vs-fp32 footprint ratio + resident-seq
                          capacity
  serve/fused_ctx<N>    — us per decode step at max_ctx=N, fused route
  serve/unfused_ctx<N>  — same engine load, gather-then-attend route
  serve/decode_fusion   — fused-vs-unfused step-time ratio at the largest
                          context config
  serve/decode_path     — fused_active=True/False per route, from the
                          decode-step jaxpr (CI fails on a silent fallback)

Scale knobs: REPRO_BENCH_FAST halves the request count and drops the
highest rate + largest context; the arch is the reduced granite-3-8b (CPU
scale).
"""
from __future__ import annotations

import os

from .common import emit

ARCH = "granite-3-8b"


def _measure_decode(engine, n_lanes: int, prompt_len: int, max_new: int):
    """Fill every lane, drain, and return (us per full-lane decode step,
    steps measured) — deltas against the engine counters, so repeated
    measurements never reset engine/watchdog state."""
    import numpy as np

    wall0, steps0 = engine.decode_wall_s, engine.decode_steps
    for i in range(n_lanes):
        engine.submit(np.arange(1 + i, prompt_len + 1 + i), max_new)
    engine.drain()
    steps = engine.decode_steps - steps0
    return ((engine.decode_wall_s - wall0) / max(1, steps)) * 1e6, steps


def _fused_vs_unfused(ctxs, fast: bool):
    from repro.serving import fused_decode_active, make_engine

    n_rep = 2 if fast else 3
    ratio_at_largest = None
    for i, ctx in enumerate(ctxs):
        engines, us = {}, {}
        for fused in (True, False):
            # 128-token pages: the TPU paged kernel needs lane-aligned
            # pages, smaller ones take the op's oracle even when fused
            eng = make_engine(ARCH, mode="native", fuse_kernels=fused,
                              max_lanes=4, page_size=128, max_ctx=ctx)
            active = fused_decode_active(eng)
            if i == 0:      # route report once per polarity (CI greps it)
                emit("serve/decode_path", 0.0,
                     f"fuse_kernels={fused};fused_active={active}")
            # a fused engine that silently took the gather route (or vice
            # versa) invalidates the comparison — fail loudly
            assert active == fused, (
                f"silent decode-route fallback: fuse_kernels={fused} "
                f"resolved to fused_active={active}")
            eng.submit([1, 2, 3, 4], 2)       # warm prefill/decode traces
            eng.drain()
            engines[fused] = eng
        # alternate routes, keep the min-of-n per route: back-to-back
        # interleaving cancels machine drift that a single pass cannot
        steps = 0
        for _ in range(n_rep):
            for fused, eng in engines.items():
                t, steps = _measure_decode(eng, 4, 8, ctx - 16)
                label = "fused" if fused else "unfused"
                us[label] = min(us.get(label, t), t)
        for fused in engines:
            label = "fused" if fused else "unfused"
            emit(f"serve/{label}_ctx{ctx}", us[label],
                 f"steps={steps};reps={n_rep};fused_active={fused}")
        ratio_at_largest = us["unfused"] / max(us["fused"], 1e-9)
    emit("serve/decode_fusion", 0.0,
         f"fused_vs_unfused={ratio_at_largest:.2f}x;ctx={ctxs[-1]}")


def main():
    import jax

    from repro.configs import get
    from repro.core import preset
    from repro.models import build_model
    from repro.serving import (Engine, naive_serve, poisson_traffic,
                               run_load, shared_prefix_traffic)

    fast = bool(os.environ.get("REPRO_BENCH_FAST"))
    n_requests = 6 if fast else 12
    rates = (4.0, 16.0) if fast else (4.0, 16.0, 64.0)
    gen_lens = (4, 8) if fast else (4, 8, 12)
    mid_rate = 16.0

    model = build_model(get(ARCH).reduced(), preset("full8", "native"))
    params = model.init(jax.random.PRNGKey(0))

    def traffic_at(rate):
        return poisson_traffic(rate=rate, n_requests=n_requests,
                               prompt_lens=(8, 16, 24), gen_lens=gen_lens,
                               vocab=128, seed=7)

    engine_tokps = 0.0
    pool_rep = None
    breakdown = {}                       # mode -> metrics at mid_rate
    for mode in ("monolithic", "chunked"):
        suffix = "" if mode == "monolithic" else "_chunked"
        for rate in rates:
            engine = Engine(model, params, max_lanes=4, page_size=8,
                            max_ctx=48, prefill_mode=mode, prefill_chunk=2)
            _, m = run_load(engine, traffic_at(rate))
            us = (m["decode_wall_s"] / max(1, m["decode_steps"])) * 1e6
            emit(f"serve/rate{rate:g}{suffix}", us,
                 f"tokps={m['decode_tok_s']:.2f};"
                 f"ttft_ms_mean={m['ttft_mean_s'] * 1e3:.1f};"
                 f"ttft_ms_p50={m['ttft_p50_s'] * 1e3:.1f};"
                 f"ttft_ms_p99={m['ttft_p99_s'] * 1e3:.1f};"
                 f"ttft_ms_max={m['ttft_max_s'] * 1e3:.1f};"
                 f"tpot_ms_p50={m['tpot_p50_s'] * 1e3:.2f};"
                 f"tpot_ms_p99={m['tpot_p99_s'] * 1e3:.2f};"
                 f"steps={m['decode_steps']};preempt={m['preemptions']};"
                 f"straggler={m['straggler_steps']}")
            if rate == mid_rate:
                breakdown[mode] = m
            if mode == "monolithic":
                engine_tokps = max(engine_tokps, m["decode_tok_s"])
                pool_rep = m.get("pool", pool_rep)
    for mode, m in breakdown.items():   # both polarities — CI greps each
        emit("serve/ttft_breakdown", 0.0,
             f"mode={mode};rate={mid_rate:g};"
             f"queue_ms={m['queue_ms_mean']:.1f};"
             f"prefill_ms={m['prefill_ms_mean']:.1f};"
             f"ttft_ms_mean={m['ttft_mean_s'] * 1e3:.1f};"
             f"ttft_ms_p99={m['ttft_p99_s'] * 1e3:.1f}")
    # the chunked TTFT claim, enforced: streaming page-sized chunks through
    # ONE prefill trace keeps even the p99 TTFT under the monolithic MEAN
    # (which eats a fresh XLA compile per novel prompt length)
    assert (breakdown["chunked"]["ttft_p99_s"]
            < breakdown["monolithic"]["ttft_mean_s"]), (
        f"chunked p99 {breakdown['chunked']['ttft_p99_s']:.3f}s >= "
        f"monolithic mean {breakdown['monolithic']['ttft_mean_s']:.3f}s")

    # radix prefix-cache sweep: same arrival process, rising fractions of
    # prompts opening with a common 2-page prefix (prompts are short, so a
    # fixed request count keeps hit-rate statistics comparable across
    # fast/full runs)
    for sharing in (0.0, 0.5, 0.9):
        engine = Engine(model, params, max_lanes=4, page_size=8, max_ctx=48,
                        prefill_mode="chunked", prefill_chunk=2,
                        radix_cache=True)
        traffic = shared_prefix_traffic(rate=mid_rate, n_requests=12,
                                        sharing=sharing, prefix_len=16,
                                        n_prefixes=1, tail_lens=(4, 8),
                                        gen_lens=gen_lens, seed=7)
        _, m = run_load(engine, traffic)
        us = (m["decode_wall_s"] / max(1, m["decode_steps"])) * 1e6
        emit("serve/prefix_hit", us,
             f"sharing={sharing:g};hit_rate={m['prefix_hit_rate']:.2f};"
             f"tokps={m['decode_tok_s']:.2f};"
             f"ttft_ms_mean={m['ttft_mean_s'] * 1e3:.1f};"
             f"queue_ms={m['queue_ms_mean']:.1f};"
             f"prefill_ms={m['prefill_ms_mean']:.1f};"
             f"shared_pages={m['pool']['shared_pages']}")

    # sharded layouts: tp=2 shard_map engine, dp=2 replica router (gated on
    # the host's device count — the 8-virtual-device CI lane sees them all)
    from repro.serving import make_router, make_sharded_engine
    n_dev = len(jax.devices())
    layouts = [(1, 1)]
    if n_dev >= 2:
        layouts += [(2, 1), (1, 2)]
    if n_dev >= 4 and not fast:
        layouts.append((2, 2))
    skw = dict(max_lanes=4, page_size=8, max_ctx=48,
               prefill_mode="chunked", prefill_chunk=2)
    for tp, dp in layouts:
        if dp == 1:
            tgt = make_sharded_engine(ARCH, tp=tp, **skw)
        else:
            tgt = make_router(ARCH, replicas=dp, tp=tp, **skw)
        _, m = run_load(tgt, traffic_at(mid_rate))
        us = (m["decode_wall_s"] / max(1, m["decode_steps"])) * 1e6
        emit("serve/sharded", us,
             f"tp={tp};dp={dp};tokps={m['decode_tok_s']:.2f};"
             f"ttft_ms_p50={m['ttft_p50_s'] * 1e3:.1f};"
             f"ttft_ms_p99={m['ttft_p99_s'] * 1e3:.1f};"
             f"tpot_ms_p50={m['tpot_p50_s'] * 1e3:.2f};"
             f"tpot_ms_p99={m['tpot_p99_s'] * 1e3:.2f};"
             f"completed={m['completed']}")

    _, nm = naive_serve(model, params, traffic_at(rates[0]))
    n_us = (nm["decode_wall_s"] / max(1, nm["decode_steps"])) * 1e6
    emit("serve/naive", n_us,
         f"tokps={nm['decode_tok_s']:.2f};steps={nm['decode_steps']}")
    emit("serve/speedup", 0.0,
         f"engine_vs_naive={engine_tokps / max(nm['decode_tok_s'], 1e-9):.2f}x")
    if pool_rep is not None:
        emit("serve/pool", 0.0,
             f"int8_vs_fp32={pool_rep['footprint_ratio']:.2f}x;"
             f"seqs_int8={pool_rep['capacity_seqs_int8']};"
             f"seqs_fp32={pool_rep['capacity_seqs_fp32']}")

    # fused-vs-unfused decode column + the dispatch-route report (the fused
    # engine must stream pages through the fused kernel and the unfused one
    # must not — a silent fallback fails the bench, and CI greps the rows)
    _fused_vs_unfused((128,) if fast else (128, 256), fast)


if __name__ == "__main__":
    main()
