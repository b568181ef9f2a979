"""Request-level serving engines on top of the jitted prefill/decode steps.

Two schedulers over the same (prefill_fn, decode_fn, params) triple:

* :class:`StaticEngine`     — the classic lockstep loop: requests are
  grouped into fixed batches in arrival order; a batch prefills together
  and decodes to the *longest* budget in the batch. This is the old
  ``serve_loop.generate`` behavior recast as a request-level scheduler
  (finished rows ride along as dead weight until the batch drains).
* :class:`ContinuousEngine` — continuous batching (Orca/vLLM-style) on a
  fixed pool of B KV slots: every decode step advances all occupied slots
  with per-slot positions; a request that hits EOS or its budget frees
  its slot *mid-stream* and the next queued request is admitted into it.

Both engines are model-agnostic: they only require

* ``prefill_fn(params, batch, cache_span) -> (logits, caches)`` where
  every cache leaf carries the batch dimension on axis 1 (the repro
  models' ``(L, B, ...)`` stacked-layer layout);
* ``decode_fn(params, caches, token, pos) -> (logits, caches)`` accepting
  a scalar ``pos`` (static) or a ``(B,)`` vector (continuous);
* ``cache_init(batch, max_len) -> caches`` to allocate the slot pool.

Tokens accumulate in a device buffer and cross to the host once per
request (continuous) or once per batch (static) — never one host sync
per token.  The engines *do* block once per decode step: per-token
latency (the Tier-2 metric) is measured per step, and the continuous
scheduler needs the per-slot done flags to make admission decisions —
that per-step host roundtrip is the scheduling cost continuous batching
pays for its occupancy win, and it is part of what we measure.
"""
from __future__ import annotations

from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from repro.serving.request import (Request, RequestMetrics, ServeReport,
                                   WallClock)
# RequestQueue lives with the other scheduling roles now; re-exported here
# because it predates the role split and callers import it from this module
from repro.serving.roles import (PrefillWorker, RequestQueue,  # noqa: F401
                                 Scheduler)


def _default_prompt_to_batch(prompts: np.ndarray) -> dict:
    """(b, prompt_len) int32 token prompts -> a prefill batch dict."""
    return {"tokens": jnp.asarray(np.asarray(prompts, np.int32))}


def _sample_tokens(logits, key, greedy: bool):
    """logits (..., V) -> token ids with the leading shape of logits."""
    if greedy:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return jax.random.categorical(key, logits).astype(jnp.int32)


# ------------------------------------------------------------------ lockstep
def decode_lockstep(decode_step: Callable, params, caches, tok0, *,
                    start_pos: int, steps: int, greedy: bool = True,
                    key=None, timer=None):
    """Lockstep decode: every row advances one token per step starting at
    ``start_pos``. Tokens accumulate in a device buffer and transfer to the
    host ONCE after the loop — the per-step ``np.asarray`` host sync the
    old loop paid is gone, so dispatch runs ahead of the device.

    With ``timer`` (a clock from :mod:`repro.serving.request`), each step
    is instead blocked and individually timed — the latency-measuring mode
    StaticEngine uses; ``step_times`` is then a list of per-step seconds.

    Returns ``(tokens, caches, step_times)`` with tokens a host
    ``(B, steps + 1)`` array (row 0 is ``tok0``).
    """
    if key is None and not greedy:
        key = jax.random.PRNGKey(0)
    B = tok0.shape[0]
    buf = jnp.zeros((B, steps + 1), jnp.int32).at[:, 0].set(tok0[:, 0])
    tok = tok0
    times: Optional[List[float]] = [] if timer is not None else None
    for i in range(steps):
        t0 = timer.now() if timer is not None else 0.0
        logits, caches = decode_step(params, caches, tok,
                                     jnp.int32(start_pos + i))
        if greedy:
            tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        else:
            key, sub = jax.random.split(key)
            tok = jax.random.categorical(sub, logits).astype(jnp.int32)
        buf = buf.at[:, i + 1].set(tok[:, 0])
        if timer is not None:
            jax.block_until_ready(tok)
            timer.charge("decode")
            times.append(timer.now() - t0)
    jax.block_until_ready(buf)
    return np.asarray(buf), caches, times


# -------------------------------------------------------------------- base
class _EngineBase:
    scheduler = "base"

    def __init__(self, prefill_fn: Callable, decode_fn: Callable, params,
                 cache_init: Callable, *, slots: int, cache_span: int,
                 eos_id: Optional[int] = None, greedy: bool = True,
                 seed: int = 0, clock=None, reject_invalid: bool = False,
                 prompt_to_batch: Callable = _default_prompt_to_batch):
        self.params = params
        # reject_invalid=True turns impossible requests into outcome
        # "rejected" metrics instead of a ValueError — the serving-facing
        # mode; tests/tools keep the strict raise as their default
        self.reject_invalid = reject_invalid
        self.cache_init = cache_init
        self.slots = slots
        self.cache_span = cache_span
        self.eos_id = eos_id
        self.greedy = greedy
        self.seed = seed
        self.clock = clock or WallClock()
        self.prompt_to_batch = prompt_to_batch
        self._decode_fn = decode_fn
        self._setup_jits(prefill_fn, decode_fn)

    def _setup_jits(self, prefill_fn, decode_fn) -> None:
        """Build the jitted entry points (the paged engine overrides this:
        its prefill/decode callables carry block tables instead of a
        monolithic batch)."""
        greedy = self.greedy

        def prefill_sample(params, batch, cache_span, key):
            logits, caches = prefill_fn(params, batch, cache_span)
            return _sample_tokens(logits[:, -1:], key, greedy), caches

        # cache_span is static: jit specializes per (prompt_len, span);
        # first-token sampling is fused in so admission is one dispatch
        self._jit_prefill = jax.jit(prefill_sample, static_argnums=(2,))
        self._jit_decode = jax.jit(
            decode_fn, donate_argnums=(1,))

    # ---- helpers shared by all schedulers
    def admission_error(self, r: Request) -> Optional[str]:
        """Why ``r`` can NEVER be served by this engine (None = servable).

        The single validation hook every scheduler routes through, so
        rejection is *symmetric*: static, continuous, and paged engines
        refuse the same impossible requests with the same message —
        rather than one scheduler raising while another admits the
        request and silently corrupts slot state past its capacity. The
        paged engine overrides this with its page-pool capacity check."""
        if r.max_new_tokens < 1:
            return f"max_new_tokens must be >= 1, got {r.max_new_tokens}"
        if r.prompt_len + r.max_new_tokens > self.cache_span:
            return (f"prompt_len + max_new_tokens "
                    f"({r.prompt_len}+{r.max_new_tokens}) exceeds "
                    f"cache_span {self.cache_span}")
        return None

    def _validate(self, requests: Sequence[Request]
                  ) -> Tuple[List[Request], List[Request]]:
        """Sort by arrival and split servable from impossible requests.
        With ``reject_invalid`` the impossible ones come back in the
        second list (outcome "rejected"); otherwise they raise."""
        reqs = sorted(requests, key=lambda r: (r.arrival_s, r.rid))
        ok: List[Request] = []
        rejected: List[Request] = []
        for r in reqs:
            err = self.admission_error(r)
            if err and not self.reject_invalid:
                raise ValueError(f"request {r.rid}: {err}")
            (rejected if err else ok).append(r)
        return ok, rejected

    @staticmethod
    def _make_metrics(reqs: Sequence[Request], rejected: Sequence[Request]
                      ) -> Dict[int, RequestMetrics]:
        """Per-request metrics for a run; rejected requests are terminal
        immediately (never admitted, never scheduled)."""
        metrics = {
            r.rid: RequestMetrics(rid=r.rid, prompt_len=r.prompt_len,
                                  arrival_s=r.arrival_s)
            for r in (*reqs, *rejected)}
        for r in rejected:
            metrics[r.rid].outcome = "rejected"
        return metrics

    def _prefill_one_batch(self, prompts: np.ndarray, key):
        """Prefill (b, L) prompts; returns (tok0 (b,1), caches)."""
        batch = self.prompt_to_batch(prompts)
        tok0, caches = self._jit_prefill(self.params, batch,
                                         self.cache_span, key)
        jax.block_until_ready(tok0)
        self.clock.charge("prefill")
        return tok0, caches

    def warmup(self, prompt_len: int) -> None:
        """Trigger jit compiles (prefill at prompt_len + decode steps)
        outside the measured run — one full slot pool of dummy requests,
        so the static engine also compiles its full-batch prefill."""
        budget = max(1, min(2, self.cache_span - prompt_len))
        self.run([Request(rid=-1 - i, prompt=np.ones(prompt_len, np.int32),
                          max_new_tokens=budget)
                  for i in range(self.slots)])

    def run(self, requests: Sequence[Request]) -> ServeReport:
        raise NotImplementedError


# ------------------------------------------------------------------ static
class StaticEngine(_EngineBase):
    """Lockstep batch-at-a-time scheduling: the old ``generate`` loop as a
    request-level scheduler. Each batch admits together (waiting for its
    slowest arrival), prefills together, and decodes to the longest budget
    in the batch; rows that finish early occupy their slot doing useless
    work until the batch drains. Requests within one batch must share a
    prompt length (no padding path).

    SLO semantics: lockstep batches cannot free a row mid-flight, so
    priorities are ignored (arrival-order batching — the baseline the
    preempting schedulers are measured against) and deadline misses are
    detected after the batch drains — but credited by the same
    :meth:`Scheduler.deadline_truncate` rule the per-step reapers use:
    only tokens whose decode step finished by the deadline count as
    generated (the lane kept lockstepping past it, but that work is
    wasted, not goodput), so an expired request no longer over-counts
    ``new_tokens`` relative to the continuous/paged engines."""

    scheduler = "static"

    def run(self, requests: Sequence[Request]) -> ServeReport:
        sched = Scheduler(self)
        reqs, rejected = sched.validate(requests)
        B = self.slots
        clock = self.clock
        t0 = clock.now()
        key = jax.random.PRNGKey(self.seed)
        metrics = self._make_metrics(reqs, rejected)
        slot_tokens = np.zeros(B, np.int64)
        decode_steps = prefills = peak_conc = 0

        for start in range(0, len(reqs), B):
            chunk = reqs[start:start + B]
            plens = {r.prompt_len for r in chunk}
            if len(plens) > 1:
                raise ValueError(
                    "StaticEngine requires equal prompt lengths within a "
                    f"batch, got {sorted(plens)} — bucket the workload or "
                    "use the continuous scheduler")
            # the whole batch waits for its slowest member
            clock.wait_until(t0 + max(r.arrival_s for r in chunk))
            t_adm = clock.now() - t0
            prompts = np.stack([np.asarray(r.prompt, np.int32)
                                for r in chunk])
            if len(chunk) < B:
                # pad a partial final batch to full width (dummy rows are
                # discarded) so the prefill/decode shapes — and their
                # warmup()-time compiles — are identical for every chunk
                prompts = np.concatenate(
                    [prompts, np.repeat(prompts[:1], B - len(chunk), 0)])
            key, sub = jax.random.split(key)
            tok0, caches = self._prefill_one_batch(prompts, sub)
            prefills += 1
            peak_conc = max(peak_conc, len(chunk))
            t_first = clock.now() - t0
            budget_max = max(r.max_new_tokens for r in chunk)
            key, sub = jax.random.split(key)
            toks, caches, times = decode_lockstep(
                self._jit_decode, self.params, caches, tok0,
                start_pos=chunk[0].prompt_len, steps=budget_max - 1,
                greedy=self.greedy, key=sub, timer=clock)
            decode_steps += budget_max - 1
            for i, r in enumerate(chunk):
                own = toks[i, :r.max_new_tokens]
                n = r.max_new_tokens
                if self.eos_id is not None:
                    hits = np.flatnonzero(own == self.eos_id)
                    if hits.size:
                        n = int(hits[0]) + 1
                m = metrics[r.rid]
                m.admitted_s, m.first_token_s = t_adm, t_first
                n, finish, timed_out = Scheduler.deadline_truncate(
                    t_first, times[:n - 1], r.deadline_abs_s)
                m.slot, m.new_tokens, m.tokens = i, n, own[:n]
                m.token_latencies_s = list(times[:n - 1])
                m.finish_s = finish
                if timed_out:
                    m.outcome = "timed_out"   # credited only to the SLO
                else:
                    m.finished = True
                    m.outcome = "completed"
                slot_tokens[i] += n
        return ServeReport(metrics=[metrics[r.rid] for r in (*reqs,
                                                             *rejected)],
                           scheduler=self.scheduler, slots=B,
                           makespan_s=clock.now() - t0,
                           decode_steps=decode_steps, prefills=prefills,
                           slot_tokens=slot_tokens,
                           peak_concurrency=peak_conc)


# -------------------------------------------------------------- continuous
class ContinuousEngine(_EngineBase):
    """Continuous batching over a fixed pool of B KV slots.

    Device state per slot: last token, position, active flag, generated
    count, budget, and a row of the token buffer. One fused jitted step
    decodes the whole pool with per-slot positions, samples, appends to
    the token buffer, and retires slots that hit EOS or budget; the host
    reads back only the tiny per-slot flags each step to drive admission.
    """

    scheduler = "continuous"

    def _pool_step_fn(self):
        decode_fn, greedy, eos_id = self._decode_fn, self.greedy, self.eos_id

        def pool_step(params, caches, state, key):
            logits, caches = decode_fn(params, caches, state["tok"],
                                       state["pos"])
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
            return caches, {
                "tok": jnp.where(active, tok, state["tok"][:, 0])[:, None],
                "pos": state["pos"] + active.astype(jnp.int32),
                "active": active & ~stop,
                "ncount": ncount,
                "budget": state["budget"],
                "tokbuf": tokbuf,
            }

        return jax.jit(pool_step,
                       donate_argnums=(1, 2))

    def _admit_fn(self):
        """One fused dispatch per admission: insert the prefilled caches
        into the slot (traced index — one compile for every slot) and set
        the slot's scheduler state."""

        def admit(caches, state, one, tok0, slot, plen, budget, active0):
            caches = jax.tree.map(
                lambda pool, o: jax.lax.dynamic_update_index_in_dim(
                    pool, o[:, 0], slot, axis=1), caches, one)
            t0 = tok0[0, 0]
            return caches, {
                "tok": state["tok"].at[slot, 0].set(t0),
                "pos": state["pos"].at[slot].set(plen),
                "active": state["active"].at[slot].set(active0),
                "ncount": state["ncount"].at[slot].set(1),
                "budget": state["budget"].at[slot].set(budget),
                "tokbuf": state["tokbuf"].at[slot, 0].set(t0),
            }

        return jax.jit(admit,
                       donate_argnums=(0, 1))

    def run(self, requests: Sequence[Request]) -> ServeReport:
        sched = Scheduler(self)
        reqs, rejected = sched.validate(requests)
        pw = PrefillWorker(self)
        B = self.slots
        clock = self.clock
        t0 = clock.now()
        key = jax.random.PRNGKey(self.seed)
        if not hasattr(self, "_pool_step"):
            self._pool_step = self._pool_step_fn()
            self._admit = self._admit_fn()
        # token buffer sized by the cache span (an upper bound on any
        # budget) so the pool step's shape — and its jit compile — is
        # stable across runs with different budget mixes
        T = self.cache_span
        caches = self.cache_init(B, self.cache_span)
        state = {
            "tok": jnp.zeros((B, 1), jnp.int32),
            "pos": jnp.zeros((B,), jnp.int32),
            "active": jnp.zeros((B,), bool),
            "ncount": jnp.zeros((B,), jnp.int32),
            "budget": jnp.ones((B,), jnp.int32),
            "tokbuf": jnp.zeros((B, T), jnp.int32),
        }
        metrics = self._make_metrics(reqs, rejected)
        slot_rid: List[Optional[int]] = [None] * B
        active_host = np.zeros(B, bool)
        slot_tokens = np.zeros(B, np.int64)
        decode_steps = prefills = peak_conc = 0

        while sched.queue or active_host.any():
            # ---- Scheduler role: reap queued then active requests past SLO
            now_rel = clock.now() - t0
            for r in sched.reap_queued(now_rel):
                metrics[r.rid].outcome = "timed_out"
            doomed = sched.doomed_slots(now_rel, slot_rid, active_host)
            if doomed:
                ncounts = np.asarray(state["ncount"])
                for s in doomed:
                    m = metrics[slot_rid[s]]
                    m.outcome = "timed_out"
                    m.new_tokens = int(ncounts[s])
                    m.finish_s = now_rel
                    m.tokens = np.asarray(
                        state["tokbuf"][s, :m.new_tokens])
                    slot_rid[s] = None
                    active_host[s] = False
                # retire the lanes on device too, so the pool step
                # stops advancing (and charging for) the dead rows
                keep = jnp.asarray(active_host)
                state["active"] = state["active"] & keep
            # ---- admission: free slot + arrived request -> prefill into it
            while sched.queue and not active_host.all():
                req = sched.peek_best(clock.now() - t0)
                if req is None:
                    break
                sched.take(req)
                slot = int(np.flatnonzero(~active_host)[0])
                m = metrics[req.rid]
                m.admitted_s = clock.now() - t0
                m.slot = slot
                key, sub = jax.random.split(key)
                tok0, one = pw.prefill_batch(
                    np.asarray(req.prompt, np.int32)[None, :], sub)
                prefills += 1
                # the admitted request holds its slot's KV from here even
                # if it finishes on its first token — count it, matching
                # the paged engine's owner-based accounting
                peak_conc = max(peak_conc, int(active_host.sum()) + 1)
                m.first_token_s = clock.now() - t0
                m.new_tokens = 1
                # the first token only crosses to the host when the
                # scheduler must inspect it (EOS check / 1-token budget)
                done0 = req.max_new_tokens == 1
                if self.eos_id is not None:
                    done0 = done0 or int(tok0[0, 0]) == self.eos_id
                caches, state = self._admit(
                    caches, state, one, tok0, slot, req.prompt_len,
                    req.max_new_tokens, not done0)
                slot_tokens[slot] += 1        # the prefill-produced token
                if done0:
                    m.finished = True
                    m.outcome = "completed"
                    m.finish_s = m.first_token_s
                    m.tokens = np.asarray([int(tok0[0, 0])], np.int32)
                else:
                    active_host[slot] = True
                    slot_rid[slot] = req.rid
            if not active_host.any():
                if sched.queue:    # pool idle until the next arrival
                    clock.wait_until(t0 + sched.next_arrival())
                    continue
                break
            # ---- one decode step over the whole pool
            t_step = clock.now()
            key, sub = jax.random.split(key)
            caches, state = self._pool_step(self.params, caches, state, sub)
            jax.block_until_ready(state["active"])
            clock.charge("decode")
            dur = clock.now() - t_step
            decode_steps += 1
            new_active = np.asarray(state["active"])
            ncounts = np.asarray(state["ncount"])
            for s in np.flatnonzero(active_host):
                m = metrics[slot_rid[s]]
                m.token_latencies_s.append(dur)
                m.new_tokens = int(ncounts[s])
                slot_tokens[s] += 1
                if not new_active[s]:           # EOS or budget: retire slot
                    m.finished = True
                    m.outcome = "completed"
                    m.finish_s = clock.now() - t0
                    m.tokens = np.asarray(state["tokbuf"][s, :m.new_tokens])
                    slot_rid[s] = None
            active_host = new_active.copy()
        return ServeReport(metrics=[metrics[r.rid] for r in (*reqs,
                                                             *rejected)],
                           scheduler=self.scheduler, slots=B,
                           makespan_s=clock.now() - t0,
                           decode_steps=decode_steps, prefills=prefills,
                           slot_tokens=slot_tokens,
                           peak_concurrency=peak_conc)


SCHEDULERS = {"static": StaticEngine, "continuous": ContinuousEngine}


def make_engine(scheduler: str, prefill_fn, decode_fn, params, cache_init,
                **kw) -> _EngineBase:
    if scheduler not in SCHEDULERS:
        # the paged + disaggregated engines register themselves on import
        # (kept out of this module to avoid circular imports)
        import repro.serving.disagg  # noqa: F401
        import repro.serving.paged  # noqa: F401
    try:
        cls = SCHEDULERS[scheduler]
    except KeyError:
        raise ValueError(f"unknown scheduler {scheduler!r}; "
                         f"expected one of {sorted(SCHEDULERS)}") from None
    return cls(prefill_fn, decode_fn, params, cache_init, **kw)
