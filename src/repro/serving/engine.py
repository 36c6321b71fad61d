"""Continuous-batching int8 serving engine.

One `Engine` drives one model family through the uniform decode-state slot
API (`decode_state_spec` / `init_slots` / `slot_from_cache` /
`paged_decode_step`): attention KV lives as int8 QTensor pages in a
`PagePool`, recurrent SSM state in dense per-lane slots — both behind the
same fused, jit-stable decode step over a padded batch of `max_lanes` lanes.

Control plane (host, numpy): `Scheduler` admission/preemption, per-lane
page tables, request bookkeeping.  Data plane (device, one trace): page
gather -> decode attention on int8 payloads -> token write-back into pages
-> sampling.  Dead lanes ride along masked (their table rows point at the
trash page and their positions never advance).

Per-step flow (Engine.step):
  1. admit new requests into free lanes (inflight batching: monolithic
     prefills join this very step's decode batch; chunked admissions start
     streaming prefill work)
  2. chunked mode only: run up to `prefill_budget` prompt tokens of
     prefill work — page-sized chunks through ONE jit-stable trace plus a
     ragged tail token-by-token — interleaved with decode so a long prompt
     never blocks running lanes for more than one budget's worth of work
  3. allocate decode pages at page boundaries; preempt the longest-context
     request when the pool is exhausted (recompute preemption)
  4. one fused decode step over all DECODE lanes (mid-prefill lanes ride
     along masked: their table rows zero to the trash page); append
     sampled tokens
  5. retire finished requests, unref their pages

Prefix sharing (DESIGN.md §10): with `radix_cache=True` the chunked
engine fronts the pool with a `RadixCache` — admission looks up the
longest page-aligned cached prefix (refs those pages instead of
recomputing them), finished prefills publish their full prompt pages, and
allocation pressure evicts LRU tree-only subtrees before preempting live
requests.  Chunked prefill makes the hits exact: the page is the
quantization unit, so a page's int8 payload is a bitwise-deterministic
function of its token prefix.

The decode loop performs exactly ONE jitted device computation per step
(asserted by tests/test_serving.py): the sampling key derives inside the
fused trace (fold_in of a host counter), the device page table re-uploads
only when the host copy changed, and the single host sync per step is the
sampled-token readback.

A `StepWatchdog` (runtime/fault.py) times every fused decode step; flagged
stragglers are logged and surface in `metrics()["straggler_steps"]`.
"""
from __future__ import annotations

import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.runtime.fault import StepWatchdog

from .pool import PagePool
from .radix import RadixCache
from .scheduler import Request, RequestState, Scheduler


def greedy_token(logits, vocab: int):
    """argmax over the unpadded vocab — THE greedy sampling primitive (the
    serve example / engine / naive baselines all share this slice)."""
    return jnp.argmax(logits[..., :vocab], axis=-1).astype(jnp.int32)


def make_sampler(vocab: int, temperature: float = 0.0, top_k: int = 0):
    """(logits (B, Vp), key) -> (B,) int32 token ids.

    temperature <= 0 is greedy (key ignored); otherwise softmax sampling at
    `temperature`, optionally restricted to the top-k logits.
    """
    if temperature <= 0.0:
        return lambda logits, key: greedy_token(logits, vocab)

    def sampler(logits, key):
        lg = logits[..., :vocab] / temperature
        if top_k:
            kth = jax.lax.top_k(lg, top_k)[0][..., -1:]
            lg = jnp.where(lg < kth, -jnp.inf, lg)
        return jax.random.categorical(key, lg, axis=-1).astype(jnp.int32)
    return sampler


class Engine:
    """Continuous-batching serving engine over the paged QTensor KV pool.

    Args:
      model: a built model exposing the decode-state slot API
        (`decode_state_spec` / `init_slots` / `slot_from_cache` /
        `paged_decode_step`); params: its parameter pytree.
      max_lanes: decode batch width (padded; dead lanes ride along masked).
      page_size: tokens per KV page; n_pages: pool size (default
        1 + max_lanes * ceil(max_ctx / page_size) — every lane can hold a
        full-context request); max_ctx: per-request prompt + generation cap.
      temperature/top_k: sampling policy (0.0 = greedy); seed: PRNG seed.
      prefill_mode: "monolithic" (default — whole prompt in one prefill
        call at admission) or "chunked" (page-sized chunks streamed
        through one jit-stable trace, interleaved with decode).
      prefill_chunk: pages per chunked-prefill trace invocation;
      prefill_budget: prompt tokens of prefill work per engine step
        (default prefill_chunk * page_size — one chunk's worth).
      radix_cache: front the pool with a prefix-sharing RadixCache
        (requires prefill_mode="chunked", where pages are bitwise-
        deterministic in their token prefix, and a paged family).
      max_skip / starvation_limit: bounded-skip admission policy knobs
        (see Scheduler).
      watchdog: StepWatchdog timing each fused step; clock: time source.

    Raises ValueError if the model family is not servable, the pool
    cannot hold one max-context request (the progress guarantee), or
    radix_cache is requested without chunked prefill / a paged pool.
    """

    def __init__(self, model, params, *, max_lanes: int = 4,
                 page_size: int = 8, n_pages: int | None = None,
                 max_ctx: int = 64, temperature: float = 0.0,
                 top_k: int = 0, seed: int = 0,
                 prefill_mode: str = "monolithic", prefill_chunk: int = 4,
                 prefill_budget: int | None = None,
                 radix_cache: bool = False, max_skip: int = 4,
                 starvation_limit: int = 8,
                 watchdog: StepWatchdog | None = None, clock=time.monotonic,
                 mesh=None):
        from repro.launch.train import (make_chunked_prefill_step,
                                        make_paged_decode_step,
                                        make_prefill_token_step,
                                        tp_serving_wrap)

        self.model, self.params = model, params
        self.clock = clock
        if not hasattr(model, "decode_state_spec"):
            raise ValueError(
                f"family {model.a.family!r} has no decode-state slot API "
                "(servable: lm / vlm / moe / ssm / hybrid)")
        spec = model.decode_state_spec()
        self.paged = spec["kv_layers"] > 0
        self.tp_size = int(getattr(model, "tp_size", 1) or 1)
        self.tp_mesh = mesh if self.tp_size > 1 else None
        if self.tp_size > 1:
            # TP decode runs the step fns under shard_map (DESIGN.md §12);
            # only the chunked prefill path is wrapped — monolithic prefill
            # would need a spec per prompt length, defeating the jit-stable
            # trace the sharded engine relies on.
            if prefill_mode != "chunked":
                raise ValueError(
                    "tp_size > 1 serving requires prefill_mode='chunked' "
                    "(the sharded engine wraps only the jit-stable chunked "
                    "traces in shard_map)")
            if mesh is None:
                raise ValueError(
                    "tp_size > 1 serving needs a ('data', 'model') mesh "
                    "passed as Engine(..., mesh=...); the model itself "
                    "builds WITHOUT one (manual TP — shard_map binds the "
                    "axis names, exactly like the sharded train step)")
        self.page_size = page_size
        self.max_ctx = max_ctx
        self.n_blocks = -(-max_ctx // page_size)

        self.pool = None
        if self.paged:
            if n_pages is None:
                n_pages = 1 + max_lanes * self.n_blocks
            self.pool = PagePool(n_pages, page_size, spec["kv_layers"],
                                 spec["n_kv"], spec["dh"])
            if self.pool.usable < self.n_blocks:
                raise ValueError(
                    f"pool of {n_pages} pages cannot hold one max_ctx="
                    f"{max_ctx} request ({self.n_blocks} pages needed)")
        self.scheduler = Scheduler(self.pool, max_skip=max_skip,
                                   starvation_limit=starvation_limit)
        self.watchdog = watchdog or StepWatchdog()

        self.max_lanes = max_lanes
        self.lane_req: list[Request | None] = [None] * max_lanes
        self.table = np.zeros((max_lanes, self.n_blocks), np.int32)
        self._table_dev = None          # device mirror, rebuilt when dirty
        self.h_tokens = np.zeros((max_lanes,), np.int32)
        self.slots = model.init_slots(max_lanes)
        self._dense_axes = spec["dense_axes"]

        self.key = jax.random.PRNGKey(seed)
        self._sample_ctr = 0
        sampler = make_sampler(model.a.vocab, temperature, top_k)
        # prefill sampling: the fold_in runs inside the jit, keyed by the
        # host counter — same key stream, one dispatch
        self._sample_jit = jax.jit(
            lambda logits, ctr: sampler(logits,
                                        jax.random.fold_in(self.key, ctr)))
        scales = ((self.pool.k_scale, self.pool.v_scale)
                  if self.paged else (None, None))
        self._decode_step = make_paged_decode_step(model, sampler, *scales,
                                                   key=self.key)
        if self.tp_size > 1:
            from jax.sharding import PartitionSpec as P

            import repro.launch.shard as S
            pspecs = S.tp_param_specs(model, params)
            slot_specs = S.decode_slot_specs(model, self.slots)
            pg = S.page_pool_spec(model) if self.paged else P()
            self._decode_step = tp_serving_wrap(
                self._decode_step, mesh,
                in_specs=(pspecs, slot_specs, pg, pg, P(), P(), P()),
                out_specs=(slot_specs, pg, pg, P()))
        self._decode_jit = jax.jit(self._decode_step,
                                   donate_argnums=(1, 2, 3))
        if self.paged:
            prefill = lambda p, t, n: model.prefill(p, t, n)  # noqa: E731
        else:
            prefill = lambda p, t, n: model.prefill(p, t)     # noqa: E731
        self._prefill_jit = jax.jit(prefill, static_argnums=(2,))

        # ---- chunked prefill + radix prefix cache (DESIGN.md §10) --------
        if prefill_mode not in ("monolithic", "chunked"):
            raise ValueError(f"unknown prefill_mode {prefill_mode!r}")
        self.prefill_mode = prefill_mode
        self.chunked = prefill_mode == "chunked"
        self.radix = None
        self._pf_dense: dict[int, object] = {}  # rid -> mid-prefill state
        if self.chunked:
            self.prefill_chunk = prefill_chunk
            self.prefill_budget = (prefill_budget
                                   or prefill_chunk * page_size)
            raw_chunk = make_chunked_prefill_step(model, prefill_chunk,
                                                  *scales)
            raw_tail = make_prefill_token_step(model, *scales)
            self._dense0 = model.init_slots(1)  # zero pf-state template
            if self.tp_size > 1:
                dense_specs = S.decode_slot_specs(model, self._dense0)
                # page snapshots stack the dense state on a leading chunk
                # axis, shifting every sharded axis right by one
                snap_specs = {k: P(*((None,) + tuple(s)))
                              for k, s in dense_specs.items()}
                raw_chunk = tp_serving_wrap(
                    raw_chunk, mesh,
                    in_specs=(pspecs, dense_specs, pg, pg, P(), P(),
                              P(), P()),
                    out_specs=(dense_specs, pg, pg, P(), snap_specs))
                raw_tail = tp_serving_wrap(
                    raw_tail, mesh,
                    in_specs=(pspecs, dense_specs, pg, pg, P(), P(), P()),
                    out_specs=(dense_specs, pg, pg, P()))
            self._chunk_jit = jax.jit(raw_chunk, donate_argnums=(2, 3))
            self._tail_jit = jax.jit(raw_tail, donate_argnums=(2, 3))
            self._warmup()
        if radix_cache:
            if not self.chunked:
                raise ValueError(
                    "radix_cache requires prefill_mode='chunked' (only the "
                    "page-scoped quantization of chunked prefill makes "
                    "cached pages bitwise-exact in their token prefix)")
            if not self.paged:
                raise ValueError(
                    f"radix_cache needs a paged KV family "
                    f"(got {model.a.family!r})")
            self.radix = RadixCache(
                self.pool,
                quant_key=f"{model.a.family}/page{page_size}/{model.q}",
                store_dense=len(self._dense_axes) > 1)
            self.scheduler.cache = self.radix

        # metrics
        self.engine_steps = 0
        self.decode_steps = 0
        self.decode_wall_s = 0.0
        self.straggler_steps = 0

    # ---- submission ------------------------------------------------------

    def submit(self, prompt, max_new: int, arrival: float | None = None):
        """Queue one request.

        Args:
          prompt: (S,) int token ids (any array-like; flattened to int32);
          max_new: generation budget >= 1; arrival: submission timestamp on
          the engine clock (defaults to now — TTFT is measured from it).

        Returns:
          The request id (int), usable as the key into `drain()`'s result.

        Raises ValueError on an empty prompt, max_new < 1, or
        S + max_new > max_ctx.
        """
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if len(prompt) == 0 or max_new < 1:
            raise ValueError("need a non-empty prompt and max_new >= 1")
        if len(prompt) + max_new > self.max_ctx:
            raise ValueError(
                f"prompt ({len(prompt)}) + max_new ({max_new}) exceeds "
                f"max_ctx ({self.max_ctx})")
        req = self.scheduler.submit(
            prompt, max_new, self.clock() if arrival is None else arrival)
        return req.rid

    # ---- engine step -----------------------------------------------------

    def step(self) -> list[Request]:
        """One engine step: admit+prefill, ensure pages, fused decode.

        Returns:
          The requests that finished during this step (their `.generated`
          lists hold the sampled tokens).  One fused decode trace covers
          all live lanes; fresh admissions join the same step's batch.
        """
        finished = []
        free = [ln for ln, r in enumerate(self.lane_req) if r is None]
        for req in self.scheduler.admit(len(free)):
            if self.chunked:
                self._admit_chunked(req, free.pop(0))
            else:
                self._admit(req, free.pop(0))
                if req.done:             # max_new == 1: prefill completed it
                    self._release(req)
                    finished.append(req)

        if self.chunked:
            finished.extend(self._run_prefill_chunks())

        if self.paged:
            self._ensure_pages()

        live = [ln for ln, r in enumerate(self.lane_req)
                if r is not None and r.state is RequestState.DECODE]
        if live:
            t0 = time.monotonic()
            toks = self._decode()
            dt = time.monotonic() - t0
            self.decode_wall_s += dt
            if self.watchdog.observe(self.decode_steps, dt):
                self.straggler_steps += 1
            self.decode_steps += 1
            for ln in live:
                req = self.lane_req[ln]
                tok = int(toks[ln])
                req.generated.append(tok)
                self.h_tokens[ln] = tok
                if req.done:
                    self._release(req)
                    finished.append(req)
        self.engine_steps += 1
        now = self.clock()
        for req in finished:
            self.scheduler.finish(req, now)
        return finished

    def drain(self, max_steps: int = 100_000) -> dict[int, list[int]]:
        """Step until every submitted request completes.

        Returns:
          {request id: [generated token ids]} for all DONE requests.

        Raises RuntimeError if the queue has not emptied after max_steps.
        """
        for _ in range(max_steps):
            if (not self.scheduler.queue
                    and all(r is None for r in self.lane_req)):
                break
            self.step()
        else:
            raise RuntimeError(f"drain did not finish in {max_steps} steps")
        return {r.rid: list(r.generated)
                for r in self.scheduler.requests.values()
                if r.state is RequestState.DONE}

    # ---- admission / release / preemption --------------------------------

    def _admit(self, req: Request, lane: int) -> None:
        if req.queue_s is None:         # TTFT split: time spent QUEUED
            req.queue_s = self.clock() - req.arrival
        s = len(req.prompt)
        nb = 0
        if self.paged:
            nb = self.scheduler.pages_needed(req)  # prompt + 1 decode block
            req.page_ids = self.pool.alloc(nb, owner=req.rid)
            assert req.page_ids is not None     # admission checked capacity
        cache_len = nb * self.page_size
        cache, logits = self._prefill_jit(
            self.params, jnp.asarray(req.prompt)[None], cache_len)
        dense, kv = self.model.slot_from_cache(cache, 0)
        self.slots = _write_dense(self.slots, self._dense_axes,
                                  jnp.int32(lane), dense)
        if self.paged:
            pids = jnp.asarray(req.page_ids)
            k_req, v_req = kv                   # (L, nb*page, KV, dh) int8
            shp = (k_req.shape[0], nb, self.page_size) + k_req.shape[2:]
            self.pool.k = _scatter_pages(self.pool.k, pids,
                                         k_req.reshape(shp))
            self.pool.v = _scatter_pages(self.pool.v, pids,
                                         v_req.reshape(shp))
            self.table[lane] = 0
            self.table[lane, :nb] = req.page_ids
            self._table_dev = None

        tok0 = int(self._sample_jit(logits, self._next_ctr())[0])
        req.generated.append(tok0)
        if req.ttft is None:
            req.ttft = self.clock() - req.arrival
            req.prefill_s = req.ttft - req.queue_s
        req.lane = lane
        req.state = RequestState.DECODE
        self.lane_req[lane] = req
        self.h_tokens[lane] = tok0

    def _release(self, req: Request) -> None:
        if self.paged and req.page_ids:
            for pid in req.page_ids:    # shared pages just drop our hold
                self.pool.unref(pid)
        self._pf_dense.pop(req.rid, None)
        if req.lane >= 0:
            self.table[req.lane] = 0
            self.lane_req[req.lane] = None
            self._table_dev = None
        req.page_ids = []
        req.lane = -1

    def _preempt(self, req: Request) -> None:
        self._release(req)
        self.scheduler.preempt(req)

    def _alloc_pages(self, n: int, req: Request) -> list[int] | None:
        """Allocate under pressure: radix LRU eviction first, recompute
        preemption second.  Returns None iff `req` itself got preempted."""
        pid = self.pool.alloc(n, owner=req.rid)
        while pid is None and self.radix is not None \
                and self.radix.evictable() > 0:
            self.radix.evict(n - self.pool.free_count)
            pid = self.pool.alloc(n, owner=req.rid)
        while pid is None:
            live = [r for r in self.lane_req if r is not None]
            if not live:
                raise RuntimeError(
                    f"pool exhausted with no live lanes to preempt "
                    f"(need {n} pages, free {self.pool.free_count})")
            victim = self.scheduler.pick_victim(live)
            self._preempt(victim)
            if victim is req:
                return None
            pid = self.pool.alloc(n, owner=req.rid)
        return pid

    def _ensure_pages(self) -> None:
        """Grow DECODE lanes' page tables at block boundaries (mid-prefill
        lanes preallocated everything at admission); evict radix subtrees,
        then preempt, on exhaustion."""
        for lane in range(self.max_lanes):
            req = self.lane_req[lane]
            if req is None or req.state is not RequestState.DECODE:
                continue
            blk = req.pos // self.page_size
            if blk < len(req.page_ids):
                continue
            pid = self._alloc_pages(1, req)
            if pid is None:          # this lane itself was preempted
                continue
            self.table[lane, blk] = pid[0]
            self._table_dev = None
            req.page_ids.extend(pid)

    # ---- chunked prefill + radix prefix cache (DESIGN.md §10) ------------

    def _warmup(self) -> None:
        """Compile the chunked engine's traces ahead of the first request.

        Unlike monolithic prefill (whose jit is keyed on every distinct
        prompt length), the chunked engine runs FOUR shape-stable traces —
        chunk prefill, tail token, fused decode, sampling — so all of its
        compilation can happen at construction instead of inside the first
        requests' TTFT.  The warmup calls write only to the trash page
        (all-zero tables, n_pages=0 masks every chunk page) and the decode
        slots re-initialize after, so no observable state survives."""
        zrow = jnp.zeros((1, self.n_blocks), jnp.int32)
        toks = jnp.zeros((self.prefill_chunk * self.page_size,), jnp.int32)
        _, kp, vp, lg, _ = self._chunk_jit(
            self.params, self._dense0, *self._pages_for_jit(), zrow,
            toks, np.int32(0), np.int32(0))
        self._store_pages(kp, vp)
        _, kp, vp, _ = self._tail_jit(
            self.params, self._dense0, *self._pages_for_jit(), zrow,
            jnp.zeros((1,), jnp.int32), jnp.zeros((1,), jnp.int32))
        self._store_pages(kp, vp)
        # the ctr=0 key is never used live (the counter pre-increments)
        self._sample_jit(lg, np.int32(0))
        slots = dict(self.slots, pos=jnp.zeros((self.max_lanes,), jnp.int32))
        _, kp, vp, _ = self._decode_jit(
            self.params, slots, *self._pages_for_jit(),
            jnp.asarray(self.table), jnp.asarray(self.h_tokens),
            np.int32(0))
        self._store_pages(kp, vp)
        self.slots = self.model.init_slots(self.max_lanes)

    def _admit_chunked(self, req: Request, lane: int) -> None:
        """Claim a lane and pages; prefill streams in later engine steps.

        Radix lookup first: the longest cached page-aligned prefix is
        reused by reference (one pool ref per hit page), only the suffix
        pages are allocated, and for recurrent families the deepest node's
        dense snapshot seeds the mid-prefill state."""
        if req.queue_s is None:
            req.queue_s = self.clock() - req.arrival
        s = len(req.prompt)
        hit_pids, hit_dense = [], None
        if self.radix is not None:
            hit_pids, hit_dense = self.radix.lookup(req.prompt)
            for pid in hit_pids:
                self.pool.ref(pid)      # the request's hold on the hit
        req.n_shared = len(hit_pids)
        req.pf_pos = req.n_shared * self.page_size
        req.page_snaps = [None] * (s // self.page_size)
        if self.paged:
            nb_total = s // self.page_size + 1   # prompt + 1 decode block
            new_pids = self._alloc_pages(nb_total - req.n_shared, req)
            assert new_pids is not None  # not in lane_req yet: no self-kill
            req.page_ids = list(hit_pids) + new_pids
            self.table[lane] = 0
            self.table[lane, :nb_total] = req.page_ids
            self._table_dev = None
        self._pf_dense[req.rid] = (hit_dense if hit_dense is not None
                                   else self._dense0)
        req.lane = lane
        self.lane_req[lane] = req       # PREFILL state: masked in decode

    def _run_prefill_chunks(self) -> list[Request]:
        """Advance every mid-prefill lane by up to `prefill_budget` prompt
        tokens: full pages through the chunked trace (page-scoped
        quantization — the radix determinism unit), then the ragged tail
        token-by-token through the decode body.  Completing lanes sample
        their first token and publish their pages to the radix tree."""
        finished: list[Request] = []
        budget = self.prefill_budget
        page = self.page_size
        for lane in range(self.max_lanes):
            if budget <= 0:
                break
            req = self.lane_req[lane]
            if req is None or req.state is not RequestState.PREFILL:
                continue
            s = len(req.prompt)
            nb_full = s // page
            lg = None
            while budget >= page and req.pf_pos < nb_full * page:
                start = req.pf_pos // page
                allowed = min(self.prefill_chunk, nb_full - start,
                              budget // page)
                toks = np.zeros((self.prefill_chunk * page,), np.int32)
                chunk = req.prompt[start * page:(start + allowed) * page]
                toks[:len(chunk)] = chunk
                dn, kp, vp, lg, snaps = self._chunk_jit(
                    self.params, self._pf_dense[req.rid],
                    *self._pages_for_jit(), self._lane_table(lane),
                    jnp.asarray(toks), np.int32(start),
                    np.int32(start + allowed))
                self._store_pages(kp, vp)
                self._pf_dense[req.rid] = dn
                if self.radix is not None and self.radix.store_dense:
                    for j in range(allowed):
                        req.page_snaps[start + j] = jax.tree.map(
                            lambda a, j=j: a[j], snaps)
                req.pf_pos = (start + allowed) * page
                budget -= allowed * page
            while budget >= 1 and nb_full * page <= req.pf_pos < s:
                dn, kp, vp, lg = self._tail_jit(
                    self.params, self._pf_dense[req.rid],
                    *self._pages_for_jit(), self._lane_table(lane),
                    jnp.asarray(req.prompt[req.pf_pos:req.pf_pos + 1]),
                    jnp.full((1,), req.pf_pos, jnp.int32))
                self._store_pages(kp, vp)
                self._pf_dense[req.rid] = dn
                req.pf_pos += 1
                budget -= 1
            if req.pf_pos >= s:         # lg is this lane's final logits
                self._finish_prefill(req, lane, lg)
                if req.done:             # max_new == 1
                    self._release(req)
                    finished.append(req)
        return finished

    def _finish_prefill(self, req: Request, lane: int, logits) -> None:
        """Prefill done: sample the first token, move the mid-prefill dense
        state into the lane's decode slot, flip to DECODE, and publish the
        full prompt pages to the radix tree (deduping against concurrent
        identical prefills that published first)."""
        tok0 = int(self._sample_jit(logits, self._next_ctr())[0])
        req.generated.append(tok0)
        if req.ttft is None:
            req.ttft = self.clock() - req.arrival
            req.prefill_s = req.ttft - req.queue_s
        dense = self._pf_dense.pop(req.rid)
        self.slots = _write_dense(self.slots, self._dense_axes,
                                  jnp.int32(lane),
                                  _squeeze_dense(dense, self._dense_axes))
        req.state = RequestState.DECODE
        self.h_tokens[lane] = tok0
        self._table_dev = None          # lane unmasks in the decode table
        if self.radix is not None:
            nb_full = len(req.prompt) // self.page_size
            if nb_full:
                dedup = self.radix.insert(req.prompt,
                                          req.page_ids[:nb_full],
                                          req.page_snaps)
                for blk, cached in dedup.items():
                    self.pool.ref(cached)           # byte-identical page:
                    self.pool.unref(req.page_ids[blk])  # swap to cached
                    req.page_ids[blk] = cached
                    self.table[lane, blk] = cached
        req.page_snaps = []

    def _lane_table(self, lane: int):
        """One lane's page-table row as the (1, NB) view the B=1 prefill
        traces expect."""
        return jnp.asarray(self.table[lane:lane + 1])

    def _pages_for_jit(self):
        if self.paged:
            return self.pool.k, self.pool.v
        return jnp.zeros((0,), jnp.int8), jnp.zeros((0,), jnp.int8)

    def _store_pages(self, kp, vp) -> None:
        if self.paged:
            self.pool.k, self.pool.v = kp, vp

    # ---- fused decode ----------------------------------------------------

    def _decode(self) -> np.ndarray:
        pos = np.zeros((self.max_lanes,), np.int32)
        for ln, req in enumerate(self.lane_req):
            if req is not None and req.state is RequestState.DECODE:
                pos[ln] = req.pos
        slots = dict(self.slots, pos=jnp.asarray(pos))
        if self.paged:
            kp, vp = self.pool.k, self.pool.v
        else:       # distinct dummies: donated args must not alias
            kp = jnp.zeros((0,), jnp.int8)
            vp = jnp.zeros((0,), jnp.int8)
        if self._table_dev is None:     # re-upload only when tables changed
            # mid-prefill lanes decode masked: their rows point at the
            # trash page so the ride-along writes never touch real pages
            mask = np.array([r is not None
                             and r.state is not RequestState.DECODE
                             for r in self.lane_req])
            eff = self.table
            if mask.any():
                eff = self.table.copy()
                eff[mask] = 0
            self._table_dev = jnp.asarray(eff)
        new_slots, new_k, new_v, toks = self._decode_jit(
            self.params, slots, kp, vp, self._table_dev,
            jnp.asarray(self.h_tokens), self._next_ctr())
        self.slots = new_slots
        if self.paged:
            self.pool.k, self.pool.v = new_k, new_v
        # THE one host-device sync of the decode loop: the token readback
        return np.asarray(toks)

    def _next_ctr(self) -> np.int32:
        """Sampling-counter tick: the PRNG fold_in happens inside the jitted
        computations (same key stream as the legacy host-side fold)."""
        self._sample_ctr += 1
        return np.int32(self._sample_ctr)

    # ---- maintenance / metrics -------------------------------------------

    def defrag(self) -> int:
        """Compact pool pages; rewrites live page tables.  Returns moves."""
        if not self.paged:
            return 0
        mapping = self.pool.defrag()
        if mapping:
            trans = np.arange(self.pool.n_pages)
            for old, new in mapping.items():
                trans[old] = new
            self.table = trans[self.table].astype(np.int32)
            self._table_dev = None
            for req in self.lane_req:
                if req is not None:
                    req.page_ids = [int(trans[p]) for p in req.page_ids]
            if self.radix is not None:  # shared pages moved exactly once
                self.radix.remap(mapping)
        return len(mapping)

    def decode_jaxpr(self):
        """jaxpr of the fused decode step at this engine's exact shapes
        (introspection for tests / the serve bench's fusion check).

        Traces through a fresh wrapper so the inspection trace never
        shares jax's tracing cache with the live `_decode_jit` — callers
        (fused_decode_active) retrace under a patched dispatch, and a
        shared cache would hand the engine a kernel-route trace it cannot
        compile on CPU (or hand the caller the stale oracle-route one).
        """
        slots = dict(self.slots, pos=jnp.zeros((self.max_lanes,), jnp.int32))
        if self.paged:
            kp, vp = self.pool.k, self.pool.v
        else:
            kp = jnp.zeros((0,), jnp.int8)
            vp = jnp.zeros((0,), jnp.int8)
        fresh = lambda *a: self._decode_step(*a)  # noqa: E731
        return jax.make_jaxpr(fresh)(
            self.params, slots, kp, vp, jnp.asarray(self.table),
            jnp.asarray(self.h_tokens), np.int32(0))

    def metrics(self) -> dict:
        """Engine aggregates + per-request rollups.

        Returns a dict with: engine_steps, decode_steps, decode_wall_s,
        completed, generated_tokens, queue_depth, live_lanes, preemptions,
        skips (bounded-skip queue jumps), straggler_steps, ttft_mean_s /
        ttft_max_s and the TTFT split queue_ms_mean / prefill_ms_mean
        (over DONE requests), decode_tok_s, "pool" (the PagePool.report()
        dict) when paged, and "radix" (RadixCache.stats()) +
        prefix_hit_rate when the radix cache is on.
        """
        done = [r for r in self.scheduler.requests.values()
                if r.state is RequestState.DONE]
        ttfts = [r.ttft for r in done if r.ttft is not None]
        queues = [r.queue_s for r in done if r.queue_s is not None]
        prefills = [r.prefill_s for r in done if r.prefill_s is not None]
        # TPOT: decode time per generated token after the first (TTFT owns
        # the first token), per request — the tail-latency complement
        tpots = [(r.finish - r.arrival - r.ttft) / (len(r.generated) - 1)
                 for r in done
                 if r.finish is not None and r.ttft is not None
                 and len(r.generated) > 1]

        def pct(vals, q):
            return float(np.percentile(vals, q)) if vals else 0.0

        gen = sum(len(r.generated) for r in done)
        out = {
            "engine_steps": self.engine_steps,
            "decode_steps": self.decode_steps,
            "decode_wall_s": self.decode_wall_s,
            "completed": len(done),
            "generated_tokens": gen,
            "queue_depth": self.scheduler.queue_depth,
            "live_lanes": sum(r is not None for r in self.lane_req),
            "preemptions": self.scheduler.preemptions,
            "skips": self.scheduler.skips,
            "straggler_steps": self.straggler_steps,
            "ttft_mean_s": float(np.mean(ttfts)) if ttfts else 0.0,
            "ttft_max_s": float(np.max(ttfts)) if ttfts else 0.0,
            "ttft_p50_s": pct(ttfts, 50),
            "ttft_p99_s": pct(ttfts, 99),
            "tpot_mean_s": float(np.mean(tpots)) if tpots else 0.0,
            "tpot_p50_s": pct(tpots, 50),
            "tpot_p99_s": pct(tpots, 99),
            "queue_ms_mean": 1e3 * float(np.mean(queues)) if queues else 0.0,
            "prefill_ms_mean": (1e3 * float(np.mean(prefills))
                                if prefills else 0.0),
            "decode_tok_s": (gen / self.decode_wall_s
                             if self.decode_wall_s > 0 else 0.0),
        }
        if self.pool is not None:
            out["pool"] = self.pool.report(ctx_len=self.max_ctx)
        if self.radix is not None:
            out["radix"] = self.radix.stats()
            out["prefix_hit_rate"] = self.radix.hit_rate
        return out


def _write_dense(slots, axes, lane, vals):
    """Write one lane's dense decode state (batch axis differs per key)."""
    out = dict(slots)
    for name, ax in axes.items():
        if ax == 0:
            out[name] = slots[name].at[lane].set(vals[name])
        else:
            out[name] = slots[name].at[:, lane].set(vals[name])
    return out


def _squeeze_dense(dense, axes):
    """Drop the size-1 lane dim of a B=1 prefill-state tree so the values
    land in a lane slot via `_write_dense` (which indexes, not slices)."""
    return {name: (dense[name][0] if ax == 0 else dense[name][:, 0])
            for name, ax in axes.items()}


@partial(jax.jit, donate_argnums=(0,))
def _scatter_pages(pages, pids, chunk):
    """pages (L, P, page, KV, dh) <- chunk (L, nb, page, KV, dh) at pids."""
    return pages.at[:, pids].set(chunk)


def fused_decode_active(engine: Engine) -> bool:
    """Whether the engine's decode step streams KV pages through the fused
    paged-attention kernel (True) or fell back to gather-then-attend
    (False, e.g. sim mode, `fuse_kernels=False`, or pages shorter than the
    128 tokens the TPU kernel needs).

    Decided from the decode-step jaxpr with the kernel dispatch forced, so
    the route is visible regardless of backend (the CPU oracle of the
    fused op gathers internally, which would otherwise mask it): the
    gather route materializes a dense per-lane KV view — an int8
    intermediate of shape (B, NB, page, KV, dh) / (B, NB*page, KV, dh)
    outside any pallas body — while the fused route never does.
    `benchmarks/serve_bench.py` reports this and CI fails on a silent
    fallback.
    """
    from repro.kernels import ops
    if not engine.paged:
        return False
    spec = engine.model.decode_state_spec()
    kv, dh = spec["n_kv"], spec["dh"]
    b, nb, page = engine.max_lanes, engine.n_blocks, engine.page_size
    dense = {(b, nb, page, kv, dh), (b, nb * page, kv, dh)}
    orig = ops._on_tpu
    ops._on_tpu = lambda: True
    try:
        jaxpr = engine.decode_jaxpr()
    finally:
        ops._on_tpu = orig
    for _, shape, dtype in ops.eqns_outside_pallas(jaxpr.jaxpr):
        if shape in dense and dtype == jnp.int8:
            return False
    return True
