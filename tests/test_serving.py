"""Serving engine: page-pool invariants, paged-vs-contiguous bit-exactness,
engine-vs-naive greedy equivalence, preemption correctness, continuous
batching beating sequential serving on step count, watchdog wiring."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get
from repro.core import preset
from repro.models import build_model
from repro.runtime.fault import StepWatchdog
from repro.serving import (Engine, PagePool, RequestState,
                           fused_decode_active, greedy_token, make_engine,
                           make_sampler, poisson_traffic)


# --------------------------------------------------------------------------
# PagePool
# --------------------------------------------------------------------------


def _pool(n_pages=9, page_size=4):
    return PagePool(n_pages, page_size, kv_layers=2, n_kv=2, dh=4)


def test_pool_alloc_free_reuse_invariants():
    pool = _pool()
    assert pool.usable == 8 and pool.free_count == 8
    a = pool.alloc(3, owner="a")
    b = pool.alloc(5, owner="b")
    assert len(a) == 3 and len(b) == 5
    assert 0 not in a + b                       # trash page never handed out
    assert len(set(a + b)) == 8                 # no double allocation
    assert pool.in_use == 8 and pool.free_count == 0
    assert pool.alloc(1) is None                # exhausted: no partial grant
    assert pool.failed_allocs == 1
    pool.free(a)
    assert pool.free_count == 3
    c = pool.alloc(3, owner="c")
    assert set(c) == set(a)                     # freed pages are reused
    with pytest.raises(ValueError):
        pool.free([b[0], b[0]])                 # double free detected
    assert pool.peak_in_use == 8
    assert pool.allocs == 11 and pool.frees >= 3


def test_pool_pages_for_and_report_ratio():
    pool = _pool(n_pages=17, page_size=4)
    assert pool.pages_for(1) == 1 and pool.pages_for(4) == 1
    assert pool.pages_for(5) == 2
    rep = pool.report(ctx_len=16)
    # int8 payloads + tiny scale overhead vs fp32 of the same geometry
    assert rep["footprint_ratio"] > 3.9
    assert rep["capacity_seqs_int8"] >= 4 * max(1, rep["capacity_seqs_fp32"])


def test_pool_defrag_compacts_and_preserves_payloads():
    pool = _pool(n_pages=9, page_size=4)
    a = pool.alloc(2, owner="a")
    b = pool.alloc(2, owner="b")
    c = pool.alloc(2, owner="c")
    for pid in a + b + c:
        pool.k = pool.k.at[:, pid].set(jnp.int8(pid))
    pool.free(b)
    mapping = pool.defrag()
    new_a = [mapping.get(p, p) for p in a]
    new_c = [mapping.get(p, p) for p in c]
    assert sorted(new_a + new_c) == [1, 2, 3, 4]   # compacted to the front
    for old, new in zip(a + c, new_a + new_c):
        np.testing.assert_array_equal(np.asarray(pool.k[:, new]),
                                      np.full((2, 4, 2, 4), old, np.int8))
    assert pool.free_count == 4
    d = pool.alloc(4, owner="d")
    assert d is not None and len(set(d) & {1, 2, 3, 4}) == 0


# --------------------------------------------------------------------------
# paged cache == contiguous cache, engine == naive batched decode
# --------------------------------------------------------------------------


def _naive_batched(model, params, prompts, max_new, T):
    """What the engine computes, minus paging: per-request prefill, stacked
    contiguous int8 cache, jointly batched greedy serve_step loop."""
    a = model.a
    toks = []
    if a.family == "ssm":
        parts = []
        for p in prompts:
            st, logits = model.prefill(params, jnp.asarray(p)[None])
            parts.append(st)
            toks.append(int(greedy_token(logits, a.vocab)[0]))
        cache = {k: jnp.concatenate([c[k] for c in parts],
                                    axis=0 if k == "pos" else 1)
                 for k in parts[0]}
    else:
        cache = model.init_cache(len(prompts), T)
        for b, p in enumerate(prompts):
            c, logits = model.prefill(params, jnp.asarray(p)[None], T)
            for k in ("k", "v", "m_conv", "m_h"):
                if k in cache:
                    cache[k] = cache[k].at[:, b].set(c[k][:, 0])
            cache["pos"] = cache["pos"].at[b].set(len(p))
            toks.append(int(greedy_token(logits, a.vocab)[0]))
    gens = [[t] for t in toks]
    step = jax.jit(model.serve_step)
    tok = jnp.asarray(toks, jnp.int32)
    for _ in range(max_new - 1):
        cache, logits = step(params, cache, tok)
        tok = greedy_token(logits, a.vocab)
        for b in range(len(prompts)):
            gens[b].append(int(tok[b]))
    return gens


PROMPTS = [np.arange(1, 9), np.arange(3, 15)]


@pytest.mark.parametrize("arch,mode", [("granite-3-8b", "native"),
                                       ("granite-3-8b", "sim"),
                                       ("granite-moe-1b-a400m", "native"),
                                       ("zamba2-7b", "native"),
                                       ("falcon-mamba-7b", "native")])
def test_engine_matches_naive_batched_decode(arch, mode):
    """Same-arrival batch: the continuous-batching engine greedy-decodes
    EXACTLY the tokens of the naive contiguous-cache serve_step loop."""
    eng = make_engine(arch, mode=mode, max_lanes=2, page_size=4, max_ctx=32)
    rids = [eng.submit(p, 6) for p in PROMPTS]
    out = eng.drain()
    naive = _naive_batched(eng.model, eng.params, PROMPTS, 6, 32)
    for b, rid in enumerate(rids):
        assert out[rid] == naive[b], (arch, mode, b)


def test_qtensor_pages_roundtrip_contiguous_cache():
    """Prefill KV written through the pool and gathered back is bit-exact
    against the contiguous int8 cache it came from."""
    from repro.kernels.ops import page_gather_op
    eng = make_engine("granite-3-8b", mode="native", max_lanes=2,
                      page_size=4, max_ctx=32)
    prompt = np.arange(1, 12)
    model, params = eng.model, eng.params
    nb = len(prompt) // 4 + 1
    cache, _ = model.prefill(params, jnp.asarray(prompt)[None], nb * 4)
    rid = eng.submit(prompt, 4)          # stays live after one step
    eng.step()
    req = eng.scheduler.requests[rid]
    assert req.state is RequestState.DECODE
    table = jnp.asarray(eng.table[req.lane][None, :])
    # pool pages are (L, P, page, KV, dh): gather each layer's arena
    gathered = jax.vmap(lambda pages: page_gather_op(pages, table))(
        eng.pool.k)                              # (L, 1, NB, page, KV, dh)
    ln, _, nb_all, pg = gathered.shape[:4]
    flat = gathered.reshape(ln, nb_all * pg, *gathered.shape[4:])
    s = len(prompt)
    np.testing.assert_array_equal(np.asarray(flat[:, :s]),
                                  np.asarray(cache["k"][:, 0, :s]))


def test_preemption_page_table_correctness():
    """Pool too small for three long generations: the engine preempts,
    requeues, and still completes everything with exact token counts and
    clean page accounting."""
    eng = make_engine("granite-3-8b", mode="native", max_lanes=3,
                      page_size=4, max_ctx=40, n_pages=11)
    rids = [eng.submit(np.arange(1 + i, 9 + i), 18) for i in range(3)]
    for _ in range(200):
        if (not eng.scheduler.queue
                and all(r is None for r in eng.lane_req)):
            break
        eng.step()
        # invariant: live lanes' tables list distinct non-trash pages
        live_pids = []
        for req in eng.lane_req:
            if req is None:
                continue
            nb = len(req.page_ids)
            row = eng.table[req.lane]
            assert list(row[:nb]) == req.page_ids
            assert all(p != 0 for p in req.page_ids)
            assert (row[nb:] == 0).all()
            live_pids += req.page_ids
        assert len(live_pids) == len(set(live_pids))     # no page shared
        assert len(live_pids) == eng.pool.in_use         # no leaks
    m = eng.metrics()
    assert m["completed"] == 3
    assert m["preemptions"] > 0                          # policy did fire
    assert eng.pool.in_use == 0                          # all freed
    for rid in rids:
        req = eng.scheduler.requests[rid]
        assert req.state is RequestState.DONE
        assert len(req.generated) == 18


def test_admission_wave_reserves_pool_capacity():
    """Two requests each needing 5 pages, 8 usable: one admission wave must
    not over-commit the pool (the second request waits its turn)."""
    eng = make_engine("granite-3-8b", mode="native", max_lanes=2,
                      page_size=4, max_ctx=32, n_pages=9)
    r0 = eng.submit(np.arange(1, 17), 4)
    r1 = eng.submit(np.arange(2, 18), 4)
    eng.step()
    states = {rid: eng.scheduler.requests[rid].state for rid in (r0, r1)}
    assert states[r0] is RequestState.DECODE
    assert states[r1] is RequestState.QUEUED
    out = eng.drain()
    assert len(out[r0]) == 4 and len(out[r1]) == 4


def test_engine_beats_sequential_on_step_count():
    """Staggered arrivals: continuous batching overlaps decode work, so the
    engine needs strictly fewer fused steps than sequential serving needs
    serve_step calls (the deterministic core of the throughput claim)."""
    eng = make_engine("granite-3-8b", mode="native", max_lanes=3,
                      page_size=4, max_ctx=32)
    eng.submit(np.arange(1, 9), 10)
    eng.step(); eng.step()
    eng.submit(np.arange(2, 10), 10)
    eng.step(); eng.step()
    eng.submit(np.arange(3, 11), 10)
    eng.drain()
    naive_steps = 3 * (10 - 1)
    assert eng.metrics()["completed"] == 3
    assert eng.decode_steps < naive_steps


def test_engine_watchdog_surfaces_stragglers():
    """Every fused decode step is timed; with a zero-tolerance deadline the
    post-warmup steps all flag and surface in the metrics."""
    wd = StepWatchdog(factor=0.0, warmup=1)
    eng = make_engine("granite-3-8b", mode="native", max_lanes=2,
                      page_size=4, max_ctx=32, watchdog=wd)
    eng.submit(np.arange(1, 9), 6)
    eng.drain()
    assert len(wd.times) == eng.decode_steps == 5
    assert eng.metrics()["straggler_steps"] == len(wd.flags) > 0


@pytest.mark.parametrize("arch", ["granite-3-8b", "granite-moe-1b-a400m",
                                  "zamba2-7b"])
def test_fused_decode_bitexact_vs_unfused(arch):
    """Acceptance: the fused paged-attention decode greedy-decodes EXACTLY
    the tokens of the gather-then-attend route, per model family, and the
    jaxpr-level route check agrees with the QConfig toggle.  The TPU
    kernel needs 128-token pages: with 4-token pages even the fused
    toggle decodes through the op's (counted) oracle."""
    outs = {}
    for fused in (True, False):
        eng = make_engine(arch, mode="native", fuse_kernels=fused,
                          max_lanes=2, page_size=4, max_ctx=32)
        assert fused_decode_active(eng) is False
        rids = [eng.submit(p, 6) for p in PROMPTS]
        res = eng.drain()
        outs[fused] = [res[r] for r in rids]
        aligned = make_engine(arch, mode="native", fuse_kernels=fused,
                              max_lanes=2, page_size=128, max_ctx=128)
        assert fused_decode_active(aligned) is fused
    assert outs[True] == outs[False], arch


def test_fresh_trace_keeps_live_decode_route_unpoisoned():
    """Inspection traces under a patched kernel dispatch must run through
    jaxpr_utils.fresh_trace: a throwaway wrapper keeps the trace out of the
    live _decode_jit's cache, so after tracing the TPU route the engine
    still decodes on the CPU-compilable one."""
    from jaxpr_utils import fresh_trace
    from repro.kernels import ops
    eng = make_engine("granite-3-8b", mode="native", max_lanes=2,
                      page_size=4, max_ctx=32)
    slots = dict(eng.slots, pos=jnp.zeros((eng.max_lanes,), jnp.int32))
    orig = ops._on_tpu
    ops._on_tpu = lambda: True
    try:
        jaxpr = fresh_trace(eng._decode_step, eng.params, slots, eng.pool.k,
                            eng.pool.v, jnp.asarray(eng.table),
                            jnp.asarray(eng.h_tokens), np.int32(0))
    finally:
        ops._on_tpu = orig
    assert any(e[0] == "pallas_call"
               for e in ops.eqns_outside_pallas(jaxpr.jaxpr))
    r = eng.submit(np.arange(1, 9), 4)     # live route still compiles
    assert len(eng.drain()[r]) == 4


def test_decode_loop_single_fused_computation_per_step():
    """The decode hot loop is one jitted computation per step: a single
    trace overall (jit-stable across occupancy changes) and exactly one
    _decode_jit call per engine step; prefill-time sampling never runs
    inside the decode loop."""
    eng = make_engine("granite-3-8b", mode="native", max_lanes=2,
                      page_size=4, max_ctx=32)
    decode_calls = []
    sample_calls = []
    real_decode, real_sample = eng._decode_jit, eng._sample_jit
    eng._decode_jit = lambda *a, **k: (decode_calls.append(1)
                                       or real_decode(*a, **k))
    eng._sample_jit = lambda *a, **k: (sample_calls.append(1)
                                       or real_sample(*a, **k))
    eng.submit(np.arange(1, 9), 8)
    eng.step(); eng.step()
    eng.submit(np.arange(2, 12), 6)          # occupancy changes mid-run
    eng.drain()
    decode_steps = eng.decode_steps
    assert len(decode_calls) == decode_steps      # one call per step
    assert len(sample_calls) == 2                 # one per ADMISSION only
    assert real_decode._cache_size() == 1         # one trace overall


def test_engine_table_mirror_invalidation():
    """The device page-table mirror re-uploads only when the host table
    changes (admission, page growth, release, defrag)."""
    eng = make_engine("granite-3-8b", mode="native", max_lanes=2,
                      page_size=4, max_ctx=32)
    eng.submit(np.arange(1, 9), 8)
    eng.step()
    dev = eng._table_dev
    assert dev is not None
    eng.step()                    # no table change: same device buffer
    assert eng._table_dev is dev
    for _ in range(20):
        if not any(eng.lane_req):
            break
        eng.step()
    assert eng.pool.in_use == 0   # released => mirror invalidated
    assert eng._table_dev is None or eng._table_dev is not dev


def test_sampler_temperature_topk():
    key = jax.random.PRNGKey(0)
    logits = jax.random.normal(key, (3, 32))
    greedy = make_sampler(16)(logits, key)
    np.testing.assert_array_equal(np.asarray(greedy),
                                  np.asarray(greedy_token(logits, 16)))
    toks = make_sampler(16, temperature=0.8, top_k=4)(logits, key)
    assert toks.shape == (3,)
    top4 = jnp.argsort(logits[:, :16], axis=-1)[:, -4:]
    for b in range(3):
        assert int(toks[b]) in set(np.asarray(top4[b]).tolist())


def test_engine_sampled_mode_runs():
    eng = make_engine("granite-3-8b", mode="native", max_lanes=2,
                      page_size=4, max_ctx=32, temperature=0.7, top_k=8)
    rid = eng.submit(np.arange(1, 9), 5)
    out = eng.drain()
    assert len(out[rid]) == 5
    assert all(0 <= t < eng.model.a.vocab for t in out[rid])


def test_engine_submit_validation_and_traffic_shapes():
    eng = make_engine("granite-3-8b", mode="native", max_lanes=2,
                      page_size=4, max_ctx=16)
    with pytest.raises(ValueError):
        eng.submit(np.arange(1, 14), 8)          # exceeds max_ctx
    with pytest.raises(ValueError):
        eng.submit(np.asarray([], np.int32), 2)
    traffic = poisson_traffic(rate=10.0, n_requests=8, prompt_lens=(4, 8),
                              gen_lens=(2, 4), vocab=64, seed=3)
    assert len(traffic) == 8
    arr = [t["arrival"] for t in traffic]
    assert arr == sorted(arr) and arr[0] > 0
    assert all(len(t["prompt"]) in (4, 8) and t["max_new"] in (2, 4)
               for t in traffic)
