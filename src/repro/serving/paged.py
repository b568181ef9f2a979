"""Paged-KV continuous batching: block-table scheduling over a page pool.

:class:`PagedEngine` keeps the continuous scheduler's slot semantics (a
fixed number of *decode lanes*) but replaces the per-slot monolithic
``cache_span`` KV reservation with a global pool of fixed-size pages
(:mod:`repro.serving.pages`):

* **admission** is gated on *enough free pages* for
  ``prompt_len + max_new_tokens`` tokens — not on a whole span — so at
  equal KV memory budget the paged engine admits strictly more
  concurrent requests whenever real requests are shorter than the span;
* **prefill is chunked**: the prompt streams through
  ``prefill_chunk_tokens``-sized chunks, each writing its K/V straight
  into the request's pages, so a long prompt never needs one contiguous
  span-sized buffer;
* **decode** runs the same fused pool step as the continuous engine,
  but through the block-table paged decode path
  (``model.decode_step_paged`` -> the Pallas paged-attention kernel on
  TPU, the gather reference elsewhere); retirement returns pages to the
  allocator's free list mid-stream.

With ``prefix_cache=True`` a :class:`~repro.serving.prefix.RadixCache`
sits between the queue and the allocator: admission looks up the longest
cached page-aligned prefix of the prompt, attaches the matched pages
read-only into the block table (one physical page, N logical owners via
the allocator's refcounts), and chunk-prefills only the uncached suffix.
When the *entire* prompt is cached, the last matched page is
copy-on-written — duplicated into a fresh page — so re-prefilling the
single token needed for first-token logits never writes a shared page.
Sequences are indexed on prefill completion (the prompt) and again on
retirement (the generated tokens — what makes a returning multi-turn
session warm); LRU refcount-1 entries are evicted when the pool runs
low. Disabled (the default), the engine byte-for-byte matches the
pre-cache scheduler.

Greedy outputs are token-identical to the monolithic engines — paging
and prefix reuse are memory-layout changes, not numerics changes — which
is what ``tools/ci_checks.py paged-parity`` and ``prefix-parity``
enforce.

Unlike the monolithic engines' ``(prefill_fn, decode_fn, cache_init)``
triple, this engine takes the *paged* triple from
:class:`repro.models.model.Model`:

* ``prefill_fn(params, caches, tokens, block_tables, start_pos)``
  (= ``model.prefill_chunk``; ``start_pos`` may land mid-page, the
  warm-suffix path),
* ``decode_fn(params, caches, token, pos, block_tables)``
  (= ``model.decode_step_paged``),
* ``cache_init(num_pages, page_size)`` (= ``model.paged_cache_init``).
"""
from __future__ import annotations

from typing import Dict, Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation

from repro.runtime import trace_names as N
from repro.serving.engine import SCHEDULERS, _EngineBase, _sample_tokens
from repro.serving.faults import FaultInjector, FaultPlan, InjectedFault
from repro.serving.pages import (PageAllocator, PoolInvariantError, PoolStats,
                                 pages_needed)
from repro.serving.prefix import RadixCache
from repro.serving.request import Request, ServeReport
from repro.serving.roles import (DecodeWorker, PageHandoff, PrefillWorker,
                                 Scheduler)


class PagedEngine(_EngineBase):
    """Continuous batching over ``slots`` decode lanes and a paged KV
    pool of ``num_pages`` pages of ``page_size`` tokens (page 0 is the
    reserved null page). ``num_pages=None`` sizes the pool to the
    monolithic engine's budget (``slots x cache_span`` tokens) plus the
    null page, so the default is budget-equivalent by construction;
    benchmarks pass an explicit pool to compare at exactly equal bytes.
    ``prefill_chunk_tokens=0`` prefills each prompt in one chunk.
    ``prefix_cache=True`` enables the prefix-sharing radix cache."""

    scheduler = "paged"

    def __init__(self, prefill_fn, decode_fn, params, cache_init, *,
                 slots: int, cache_span: int, page_size: int = 16,
                 num_pages: Optional[int] = None,
                 prefill_chunk_tokens: int = 0,
                 prefix_cache: bool = False,
                 fault_plan: Optional[FaultPlan] = None,
                 requeue_backoff_s: float = 0.0, **kw):
        self.page_size = int(page_size)
        # deterministic chaos: a FaultPlan makes run() consult a
        # FaultInjector at every engine step (see repro.serving.faults)
        self.fault_plan = fault_plan
        # delay before a preempted/faulted request re-enters the queue
        # (0.0 keeps SimClock schedules backoff-free and deterministic)
        self.requeue_backoff_s = float(requeue_backoff_s)
        # block-table width: logical pages a maximal request can touch
        self.npag_max = -(-cache_span // self.page_size)
        if num_pages is None:
            # default: every lane can hold a maximal request at once —
            # the monolithic slots*span budget, rounded up to whole pages
            num_pages = slots * self.npag_max + 1
        self.num_pages = int(num_pages)
        self.prefill_chunk_tokens = int(prefill_chunk_tokens)
        self.prefix_cache = bool(prefix_cache)
        super().__init__(prefill_fn, decode_fn, params, cache_init,
                         slots=slots, cache_span=cache_span, **kw)

    @property
    def usable_pages(self) -> int:
        return self.num_pages - 1            # minus the null page

    # --------------------------------------------------------- validation
    def admission_error(self, r: Request) -> Optional[str]:
        err = super().admission_error(r)     # budget >= 1, block-table fit
        if err:
            return err
        need = pages_needed(r.prompt_len + r.max_new_tokens, self.page_size)
        if need > self.usable_pages:
            return (f"needs {need} KV pages ({r.prompt_len}+"
                    f"{r.max_new_tokens} tokens at page_size "
                    f"{self.page_size}) but the pool has only "
                    f"{self.usable_pages} usable pages")
        return None

    # --------------------------------------------------------------- jits
    def _setup_jits(self, prefill_fn, decode_fn) -> None:
        # one compile per chunk length; start_pos stays traced
        self._jit_chunk = jax.jit(
            prefill_fn, donate_argnums=(1,))
        # copy-on-write: duplicate page src into page dst across every
        # pool leaf (axis 0 = layers, axis 1 = pages); src/dst stay
        # traced so one compile covers every divergence point
        self._jit_copy = jax.jit(
            lambda caches, src, dst: jax.tree.map(
                lambda a: a.at[:, dst].set(a[:, src]), caches),
            donate_argnums=(0,))
        greedy, eos_id = self.greedy, self.eos_id

        def pool_step(params, caches, state, key):
            logits, caches = decode_fn(params, caches, state["tok"],
                                       state["pos"], state["btab"])
            with jax.named_scope(N.SAMPLE):
                return caches, sample(logits, state, key)

        def sample(logits, state, key):
            tok = _sample_tokens(logits[:, -1], key, greedy)      # (B,)
            active = state["active"]
            ncount = state["ncount"]
            B, T = state["tokbuf"].shape
            bidx = jnp.arange(B)
            idx = jnp.minimum(ncount, T - 1)
            cur = state["tokbuf"][bidx, idx]
            tokbuf = state["tokbuf"].at[bidx, idx].set(
                jnp.where(active, tok, cur))
            ncount = ncount + active.astype(jnp.int32)
            stop = ncount >= state["budget"]
            if eos_id is not None:
                stop = stop | (tok == eos_id)
            still = active & ~stop
            return {
                "tok": jnp.where(active, tok, state["tok"][:, 0])[:, None],
                "pos": state["pos"] + active.astype(jnp.int32),
                "active": still,
                "ncount": ncount,
                "budget": state["budget"],
                "tokbuf": tokbuf,
                # retired rows point at the null page so a stale table
                # can never write into a page the allocator reissued
                "btab": jnp.where(still[:, None], state["btab"], 0),
            }

        def admit(state, tok0, btab_row, slot, plen, budget, active0):
            # no cache insertion: chunked prefill already wrote this
            # request's K/V into its own pages of the shared pool
            t0 = tok0[0, 0]
            return {
                "tok": state["tok"].at[slot, 0].set(t0),
                "pos": state["pos"].at[slot].set(plen),
                "active": state["active"].at[slot].set(active0),
                "ncount": state["ncount"].at[slot].set(1),
                "budget": state["budget"].at[slot].set(budget),
                "tokbuf": state["tokbuf"].at[slot, 0].set(t0),
                "btab": state["btab"].at[slot].set(btab_row),
            }

        def evict(state, slot):
            # retire one lane mid-flight (deadline reap / preemption):
            # deactivate it and point its block-table row at the null
            # page so a stale table can never touch a reissued page
            return {
                **state,
                "active": state["active"].at[slot].set(False),
                "btab": state["btab"].at[slot].set(
                    jnp.zeros_like(state["btab"][0])),
            }

        self._pool_step = jax.jit(
            pool_step, donate_argnums=(1, 2))
        self._admit = jax.jit(
            admit, donate_argnums=(0,))
        self._jit_evict = jax.jit(
            evict, donate_argnums=(0,))

    def warmup(self, prompt_len: int) -> None:
        # jit-compile warmup must not consume the fault schedule (every
        # run() builds a fresh injector, but warming up under chaos
        # would fail/requeue dummy requests for nothing)
        plan, self.fault_plan = self.fault_plan, None
        try:
            super().warmup(prompt_len)
        finally:
            self.fault_plan = plan

    # ----------------------------------------------------------- teardown
    def _release_pages(self, alloc: PageAllocator, rid: int) -> None:
        """Return a request's pages to the pool. Every terminal path
        (completion, deadline reap, preemption, fault failure) releases
        through this one seam — ``ci_checks.py chaos-parity`` self-tests
        its leak detection by no-op'ing this method and requiring the
        check to fail."""
        alloc.free(rid)

    # ---------------------------------------------------------- prefill
    def _chunked_prefill(self, prompt: np.ndarray, btab_dev, clock, *,
                         rid: int, start: int = 0):
        """Stream request ``rid``'s prompt positions ``[start, len)``
        through the pool in page-filling chunks; returns the last chunk's
        logits and the number of chunks run. ``start > 0`` is the warm
        path: positions below it are already resident in attached prefix
        pages, so only the suffix pays prefill compute.

        Each chunk sees only the first ``pages_needed(written)`` pages of
        the block table, so attention cost grows with the live prefix
        rather than paying the full cache_span gather on every chunk
        (one jit compile per distinct (chunk length, live pages) pair)."""
        plen = int(prompt.shape[0])
        cs = self.prefill_chunk_tokens or (plen - start)
        logits = None
        chunks = 0
        for lo in range(start, plen, cs):
            end = min(lo + cs, plen)
            with TraceAnnotation(N.PREFILL_CHUNK, rid=rid, start=lo,
                                 tokens=end - lo):
                n_live = pages_needed(end, self.page_size)
                chunk = jnp.asarray(prompt[None, lo:end])
                logits, self._caches = self._jit_chunk(
                    self.params, self._caches, chunk, btab_dev[:, :n_live],
                    jnp.int32(lo))
                jax.block_until_ready(logits)
                clock.charge("prefill")     # each chunk is a prefill dispatch
            chunks += 1
        return logits, chunks

    # --------------------------------------------------------- admission
    def _reserve_pages(self, req: Request, alloc: PageAllocator,
                      radix: Optional[RadixCache], owner=None):
        """Try to reserve pages for ``req``, reusing the longest cached
        prefix when the radix cache is on. Returns
        ``(pages, suffix_start)`` or ``None`` when the pool (even after
        LRU eviction) cannot cover the fresh remainder — the caller
        blocks the queue head until a retirement frees pages. ``owner``
        is the allocator key the reservation is held under (default: the
        rid; the prefill role reserves under its own key and hands off —
        see :class:`repro.serving.roles.PageHandoff`).

        The suffix start is capped at ``prompt_len - 1``: at least one
        prompt token must be re-prefilled to produce the first-token
        logits. When the whole prompt is cached that cap lands mid-page,
        so the final matched page is attached *copy-on-write* — its K/V
        is duplicated into a fresh page before the one-token prefill
        writes into it — and every fully-matched page stays read-only."""
        owner = req.rid if owner is None else owner
        total_tokens = req.prompt_len + req.max_new_tokens
        if radix is None:
            if not alloc.can_fit(total_tokens):
                return None
            return alloc.allocate(owner, total_tokens), 0
        match_pages, match_tok = radix.lookup(np.asarray(req.prompt))
        s0 = min(match_tok, req.prompt_len - 1)
        k_full = s0 // self.page_size
        shared = match_pages[:k_full]
        cow_src = match_pages[k_full] if s0 < match_tok else None
        need_fresh = pages_needed(total_tokens, self.page_size) - len(shared)
        if need_fresh > alloc.num_free:
            radix.evict(need_fresh - alloc.num_free,
                        protect=frozenset(match_pages))
        if need_fresh > alloc.num_free:
            return None
        pages = alloc.allocate(owner, total_tokens, shared=shared)
        if cow_src is not None:
            self._caches = self._jit_copy(self._caches, jnp.int32(cow_src),
                                          jnp.int32(pages[k_full]))
        return pages, s0

    # -------------------------------------------------------------- run
    def run(self, requests: Sequence[Request]) -> ServeReport:
        # role composition (interleaved): one Scheduler, one PrefillWorker
        # and one DecodeWorker over all lanes, sharing this engine's clock
        # — same schedule as the old monolithic loop (parity-gated), with
        # the page handoff made explicit between the two roles
        sched = Scheduler(self)
        reqs, rejected = sched.validate(requests)
        B = self.slots
        clock = self.clock
        t0 = clock.now()
        key = jax.random.PRNGKey(self.seed)
        with TraceAnnotation(N.POOL_INIT):
            self._caches = self.cache_init(self.num_pages, self.page_size)
            alloc = PageAllocator(self.num_pages, self.page_size)
        radix = RadixCache(alloc) if self.prefix_cache else None
        inj = FaultInjector(self.fault_plan) if self.fault_plan else None
        stats = PoolStats()
        pw = PrefillWorker(self)
        dw = DecodeWorker(self, B, npag_max=self.npag_max)
        handoff = PageHandoff(alloc, self._release_pages, self.page_size)
        metrics = self._make_metrics(reqs, rejected)
        # plen_of tracks the *current* incarnation of each request (a
        # requeue replaces the entry with the extended-prompt version;
        # the Request itself lives in sched.req_of)
        plen_of = {r.rid: r.prompt_len for r in reqs}
        prompt_of: Dict[int, np.ndarray] = {}
        # tokens a preempted/faulted request generated before eviction —
        # its terminal metrics report the cumulative stream
        partial: Dict[int, np.ndarray] = {}
        admissions = 0
        decode_steps = prefills = peak_conc = blocked = 0
        lookups = hits = tokens_saved = 0
        preempt_events = requeues = 0
        step = -1                        # engine step (admission or decode)

        def audit() -> None:
            """Under a fault plan the pool is re-checked at every event;
            a poison fault is *supposed* to trip this — the injector
            heals it and the pool must check clean again. A failure the
            injector cannot heal is real corruption and escapes."""
            if inj is None:
                return
            try:
                alloc.check()
            except PoolInvariantError:
                if not inj.heal(alloc):
                    raise
                alloc.check()

        def index_sequence(rid: int, gen_tokens: np.ndarray) -> None:
            """Index the retiring request's full pages: its prompt plus
            every generated token whose K/V was written (the final
            sampled token never reaches the pool — no decode step
            consumed it)."""
            seq = np.concatenate([
                prompt_of[rid],
                np.asarray(gen_tokens[:-1], np.int32)])
            radix.insert(seq, alloc.owned(rid))

        def cumulative(rid: int, gen: np.ndarray) -> np.ndarray:
            prev = partial.get(rid)
            gen = np.asarray(gen, np.int32)
            return gen if prev is None else np.concatenate([prev, gen])

        def requeue_or_fail(rid: int, gen: np.ndarray, now_rel: float,
                            exhausted_outcome: str) -> None:
            """Put an evicted request back in the queue with its
            generated-so-far tokens appended to its prompt (greedy
            re-prefill of the extended prompt reproduces the
            continuation exactly — and warm-restarts through the radix
            cache when enabled). After ``max_retries`` requeues the
            request goes terminal instead."""
            nonlocal requeues
            r = sched.req_of[rid]
            m = metrics[rid]
            cum = cumulative(rid, gen)
            m.retries += 1
            if m.retries > r.max_retries:
                m.outcome = exhausted_outcome
                m.finish_s = now_rel
                m.new_tokens = len(cum)
                m.tokens = cum
                return
            if len(gen):
                partial[rid] = cum
            arrival = now_rel + self.requeue_backoff_s
            nr = Request(
                rid=rid,
                prompt=np.concatenate([np.asarray(r.prompt, np.int32),
                                       np.asarray(gen, np.int32)]),
                max_new_tokens=r.max_new_tokens - len(gen),
                arrival_s=arrival,
                # the *absolute* deadline survives the requeue (an SLO
                # clock does not restart because the scheduler evicted)
                deadline_s=(None if r.deadline_abs_s is None
                            else r.deadline_abs_s - arrival),
                priority=r.priority, max_retries=r.max_retries)
            plen_of[rid] = nr.prompt_len
            sched.requeue(nr)
            requeues += 1

        def evict_lane(s: int, ncounts: np.ndarray) -> np.ndarray:
            """Take lane ``s`` out of service mid-flight: index its pages
            into the radix cache (so a requeue re-prefills warm), free
            them, null the device row. Returns the generated tokens."""
            rid = dw.slot_rid[s]
            n = int(ncounts[s])
            gen = np.asarray(dw.state["tokbuf"][s, :n])
            if radix is not None:
                index_sequence(rid, gen)
            self._release_pages(alloc, rid)
            dw.slot_rid[s] = None
            dw.active_host[s] = False
            return gen

        def try_preempt(for_req: Request) -> bool:
            """Evict the Scheduler's victim choice (lowest priority;
            ties: latest admitted — least sunk prefill), requeued with
            its progress as prompt extension. False = nobody active is
            strictly lower priority than ``for_req``."""
            nonlocal preempt_events
            victim = sched.pick_victim(for_req, dw.slot_rid,
                                       dw.active_host, dw.admit_seq)
            if victim is None:
                return False
            ncounts = np.asarray(dw.state["ncount"])
            rid = dw.slot_rid[victim]
            gen = evict_lane(victim, ncounts)
            dw.evict(victim)
            metrics[rid].preemptions += 1
            preempt_events += 1
            requeue_or_fail(rid, gen, clock.now() - t0, "preempted")
            audit()
            return True

        def reap(now_rel: float) -> None:
            """Time out queued and active requests past their deadline."""
            for r in sched.reap_queued(now_rel):
                m = metrics[r.rid]
                m.outcome = "timed_out"
                cum = cumulative(r.rid, np.zeros(0, np.int32))
                if len(cum):          # progress from before eviction
                    m.new_tokens = len(cum)
                    m.tokens = cum
                    m.finish_s = now_rel
            doomed = sched.doomed_slots(now_rel, dw.slot_rid, dw.active_host)
            if doomed:
                ncounts = np.asarray(dw.state["ncount"])
                for s in doomed:
                    rid = dw.slot_rid[s]
                    m = metrics[rid]
                    gen = evict_lane(s, ncounts)
                    dw.evict(s)
                    cum = cumulative(rid, gen)
                    m.outcome = "timed_out"
                    m.new_tokens = len(cum)
                    m.tokens = cum
                    m.finish_s = now_rel
                audit()

        while sched.queue or dw.active_host.any():
            step += 1
            with TraceAnnotation(N.STEP, step=step):
                if inj is not None:
                    inj.begin_step(step, alloc, clock)
                    audit()
                # ---- Scheduler role: reap queued then active requests
                # past SLO
                with TraceAnnotation(N.REAP):
                    now_rel = clock.now() - t0
                    reap(now_rel)
                # ---- admission: lane + arrived request + enough pages; a
                # higher-priority arrival may preempt to make room for both
                while sched.queue:
                    now_rel = clock.now() - t0
                    req = sched.peek_best(now_rel)
                    if req is None:
                        break
                    if dw.active_host.all() and not try_preempt(req):
                        break
                    if inj is not None and inj.refuse_alloc():
                        blocked += 1     # transient injected refusal: retry
                        break            # next engine step
                    # PrefillWorker role: reserve under the prefill owner key
                    got = pw.reserve(req, alloc, radix)
                    if radix is not None:
                        lookups += 1
                    while got is None and try_preempt(req):
                        got = pw.reserve(req, alloc, radix)
                    if got is None:
                        blocked += 1     # queue head waits for retirements
                        break
                    pages, s0 = got
                    with TraceAnnotation(N.ADMIT, rid=req.rid,
                                         prompt_len=req.prompt_len,
                                         cached=s0):
                        sched.take(req)
                        prompt_np = np.asarray(req.prompt, np.int32)
                        prompt_of[req.rid] = prompt_np
                        slot = dw.free_lane()
                        m = metrics[req.rid]
                        base = len(partial.get(req.rid, ()))
                        m.admitted_s = clock.now() - t0
                        m.slot = slot
                        m.cached_prompt_tokens = s0
                        if s0 > 0:
                            hits += 1
                            tokens_saved += s0
                        peak_conc = max(peak_conc, alloc.num_owners)
                        btab_row = np.zeros(self.npag_max, np.int32)
                        btab_row[:len(pages)] = pages
                        btab_dev = jnp.asarray(btab_row)[None]
                        try:
                            if inj is not None:
                                inj.check_prefill()
                            logits, chunks = pw.prefill(
                                prompt_np, btab_dev, clock, rid=req.rid,
                                start=s0)
                        except InjectedFault:
                            # contain the fault to this request: give back its
                            # pages (un-prefilled — check_prefill fires
                            # before any chunk writes) and retry or fail it
                            # alone
                            handoff.abort(req.rid)
                            audit()
                            requeue_or_fail(req.rid, np.zeros(0, np.int32),
                                            clock.now() - t0, "failed")
                            inj.note_prefill_resolved(step)
                            continue
                        prefills += chunks
                        if radix is not None:   # index the prompt's full pages
                            radix.insert(prompt_np, pages)
                        key, sub = jax.random.split(key)
                        tok0 = _sample_tokens(logits[:, -1:], sub, self.greedy)
                        if base == 0:
                            m.first_token_s = clock.now() - t0
                        m.new_tokens = base + 1
                        done0 = req.max_new_tokens == 1
                        if self.eos_id is not None:
                            done0 = done0 or int(tok0[0, 0]) == self.eos_id
                        # PageHandoff role: decode takes ownership of the
                        # pages. Interleaved, the lane picks the request up
                        # in the same engine step: there is no handoff wait
                        # to record (the disaggregated engine measures the
                        # real queue-wait)
                        handoff.transfer(req.rid)
                        dw.admit(tok0, btab_dev[0], slot, req.prompt_len,
                                 req.max_new_tokens, not done0)
                        dw.slot_tokens[slot] += 1
                        admissions += 1
                        dw.admit_seq[slot] = admissions
                        if inj is not None:
                            inj.note_admission(step)
                        if done0:
                            m.finished = True
                            m.outcome = "completed"
                            m.finish_s = clock.now() - t0
                            m.tokens = cumulative(
                                req.rid,
                                np.asarray([int(tok0[0, 0])], np.int32))
                            self._release_pages(alloc, req.rid)
                            audit()
                        else:
                            dw.active_host[slot] = True
                            dw.slot_rid[slot] = req.rid
                if not dw.active_host.any():
                    if sched.queue:
                        # pool idle until the next arrival; when admission
                        # is blocked by an injected fault instead, fall
                        # through — the engine-step counter keeps advancing
                        # so timed faults (pressure windows, refusals) can
                        # drain
                        with TraceAnnotation(N.WAIT_FOR_ARRIVAL):
                            clock.wait_until(t0 + sched.next_arrival())
                        continue
                    break
                # ---- DecodeWorker role: one fused step over all lanes
                t_step = clock.now()
                dw.note_step_start(t_step - t0)
                key, sub = jax.random.split(key)
                new_active, ncounts = dw.step(sub)
                dur = clock.now() - t_step
                dw.busy_s += dur
                decode_steps += 1
                lanes = np.flatnonzero(dw.active_host)
                for s in lanes:
                    rid = dw.slot_rid[s]
                    m = metrics[rid]
                    base = len(partial.get(rid, ()))
                    m.token_latencies_s.append(dur)
                    m.new_tokens = base + int(ncounts[s])
                    dw.slot_tokens[s] += 1
                for s in lanes[~new_active[lanes]]:  # EOS or budget
                    rid, n = dw.slot_rid[s], int(ncounts[s])
                    with TraceAnnotation(N.RETIRE, rid=rid, tokens=n):
                        m = metrics[rid]
                        m.finished = True
                        m.outcome = "completed"
                        m.finish_s = clock.now() - t0
                        gen = np.asarray(dw.state["tokbuf"][s, :n])
                        m.tokens = cumulative(rid, gen)
                        if radix is not None:
                            index_sequence(rid, gen)
                        self._release_pages(alloc, rid)
                        audit()
                        dw.slot_rid[s] = None
                dw.active_host = new_active.copy() & dw.active_host
                dw.note_step_end(clock.now() - t0)
                live = sum(plen_of[dw.slot_rid[s]] + int(ncounts[s])
                           for s in np.flatnonzero(dw.active_host))
                stats.sample(alloc, live)
        self._caches = None          # free the pool between runs
        return ServeReport(
            metrics=[metrics[r.rid] for r in (*reqs, *rejected)],
            scheduler=self.scheduler, slots=B,
            makespan_s=clock.now() - t0, decode_steps=decode_steps,
            prefills=prefills, slot_tokens=dw.slot_tokens,
            peak_concurrency=peak_conc, page_size=self.page_size,
            num_pages=self.num_pages,
            page_occupancy_mean=stats.occupancy_mean,
            page_occupancy_peak=stats.occupancy_peak,
            fragmentation_mean=stats.fragmentation_mean,
            fragmentation_peak=stats.fragmentation_peak,
            pages_high_water=alloc.high_water,
            failed_allocs=alloc.failed_allocs,
            admission_blocked_steps=blocked,
            prefix_enabled=self.prefix_cache,
            prefix_lookups=lookups, prefix_hits=hits,
            prefill_tokens_saved=tokens_saved,
            pages_shared_peak=stats.pages_shared_peak,
            prefix_evictions=radix.evictions if radix else 0,
            preemption_events=preempt_events, requeues=requeues,
            pages_leaked=alloc.owned_pages,
            faults_injected=inj.injected if inj else 0,
            fault_recoveries=inj.recoveries if inj else 0,
            fault_recovery_steps=inj.recovery_steps() if inj else [],
            handoffs=handoff.handoffs,
            decode_stalls_s=list(dw.stalls_s))


SCHEDULERS["paged"] = PagedEngine
