"""Composable serving roles: scheduler, prefill worker, decode worker,
and the page-ownership handoff between them (DESIGN.md §5).

The engines in :mod:`repro.serving.engine` / :mod:`repro.serving.paged`
used to be monolithic ``run()`` loops; this module is the role split
those loops now compose:

* :class:`Scheduler`     — admission policy: arrival-aware priority
  queueing, deadline reaping (queued and in-flight), preemption victim
  choice, and the deadline-truncation rule every engine credits tokens
  by. One scheduler per run; the engines own the device state, the
  scheduler owns *which request runs next and for how long*.
* :class:`PrefillWorker` — owns prefill compute: page reservation under
  the *prefill* role key (prefix-cache attach included) and the chunked
  prefill dispatches. The monolithic engines use its batch flavor.
* :class:`DecodeWorker`  — owns a pool of decode lanes: the fused
  pool-step device state, lane bookkeeping (which rid sits where), a
  virtual timeline for disaggregated scheduling, and decode-stall
  samples (gaps between consecutive steps while lanes stayed active —
  the prefill-interference metric).
* :class:`PageHandoff`   — the ownership transfer protocol: prefill
  reserves pages under ``("prefill", rid)``, decode takes them over
  under plain ``rid``. The transfer re-attaches every page at +1
  refcount before the prefill hold is released through the engine's
  ``_release_pages`` seam, so refcounts are conserved, the pool is
  never transiently unowned, and the RS102 free choke point (and the
  chaos-parity leak self-test behind it) still sees every release.

One *shared* page pool backs both roles — a pool-per-role design would
need a cross-pool KV copy per handoff; with shared pages the handoff is
pure bookkeeping (refcount +1/-1) and costs zero KV traffic.

The interleaved engines compose these roles in one loop (behavior
unchanged — parity-gated); :class:`repro.serving.disagg.DisaggregatedEngine`
runs separate prefill/decode worker pools over the same roles.
"""
from __future__ import annotations

from typing import Dict, Hashable, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation

from repro.runtime import trace_names as N
from repro.serving.pages import PageAllocator, PoolInvariantError
from repro.serving.request import Request


class RequestQueue:
    """Arrival-aware priority queue the continuous/paged schedulers admit
    from. Among *arrived* requests the highest ``priority`` wins; ties
    break by earliest arrival then lowest rid — so an all-default-priority
    workload admits in exactly the old FIFO order. Requeues (preemption,
    fault retry) :meth:`push` back with a fresh arrival time."""

    def __init__(self, requests: Sequence[Request] = ()) -> None:
        self._items: List[Request] = list(requests)

    def __len__(self) -> int:
        return len(self._items)

    def __bool__(self) -> bool:
        return bool(self._items)

    def push(self, req: Request) -> None:
        self._items.append(req)

    def remove(self, req: Request) -> None:
        self._items.remove(req)

    def next_arrival(self) -> float:
        return min(r.arrival_s for r in self._items)

    def peek_best(self, now_rel: float) -> Optional[Request]:
        """Highest-priority request that has arrived by ``now_rel``."""
        ready = [r for r in self._items if r.arrival_s <= now_rel]
        if not ready:
            return None
        return min(ready, key=lambda r: (-r.priority, r.arrival_s, r.rid))

    def pop_expired(self, now_rel: float) -> List[Request]:
        """Remove and return queued requests already past their deadline —
        admitting them would burn prefill on work that cannot meet its
        SLO, so the reaper retires them straight from the queue."""
        dead = [r for r in self._items
                if r.deadline_abs_s is not None and now_rel > r.deadline_abs_s]
        for r in dead:
            self._items.remove(r)
        return dead


# ------------------------------------------------------------------ handoff
def prefill_owner(rid: int) -> Tuple[str, int]:
    """Allocator owner key for pages held by the *prefill* role. The
    decode role holds under the plain ``rid`` — every pre-existing
    consumer of decode-side ownership (``alloc.owned(rid)``,
    ``_release_pages(alloc, rid)``, leak accounting) keeps working
    unchanged."""
    return ("prefill", rid)


class PageHandoff:
    """Transfer a request's pages from prefill to decode ownership.

    ``release_fn`` is the engine's bound ``_release_pages`` — the RS102
    free choke point — so every refcount drop the handoff performs goes
    through the same seam the chaos-parity leak self-test no-ops.

    :meth:`transfer` is refcount-conserving by construction: the decode
    role attaches every page at +1 *before* the prefill hold drops its
    +1, so a shared prefix page's cache reference is never the last one
    standing mid-handoff and a crash between the two halves can only
    over-hold (leak-detected), never free a live page.
    """

    def __init__(self, alloc: PageAllocator, release_fn,
                 page_size: int) -> None:
        self.alloc = alloc
        self._release = release_fn
        self.page_size = int(page_size)
        self.handoffs = 0
        self.latencies_s: List[float] = []

    def roles_of(self, rid: int) -> Tuple[bool, bool]:
        """(prefill holds, decode holds) — the dual-ownership probe the
        handoff invariant tests assert on."""
        return (self.alloc.holds(prefill_owner(rid)), self.alloc.holds(rid))

    def transfer(self, rid: int) -> List[int]:
        """Move ``rid``'s pages from the prefill hold to the decode hold.
        Raises :class:`PoolInvariantError` on a double handoff (decode
        already holds) or a handoff without a reservation (prefill holds
        nothing). Returns the transferred block table."""
        pkey = prefill_owner(rid)
        if self.alloc.holds(rid):
            raise PoolInvariantError(
                f"handoff of rid {rid}: decode role already holds pages "
                "(double handoff?)")
        if not self.alloc.holds(pkey):
            raise PoolInvariantError(
                f"handoff of rid {rid}: prefill role holds no pages "
                "(handoff without reservation?)")
        pages = self.alloc.owned(pkey)
        # attach decode-side first (+1 per page), then drop the prefill
        # hold through the engine's release seam (-1 per page): net-zero
        # refcounts, and len(pages) * page_size tokens need exactly
        # len(pages) pages, so no fresh allocation can occur here
        self.alloc.allocate(rid, len(pages) * self.page_size, shared=pages)
        self._release(self.alloc, pkey)
        self.handoffs += 1
        return pages

    def abort(self, rid: int) -> None:
        """Release the prefill-role hold without transferring — the
        containment path for a failed prefill (the request's pages go
        straight back) and the completed-at-prefill path (a 1-token
        budget or first-token EOS never reaches a decode lane)."""
        pkey = prefill_owner(rid)
        if not self.alloc.holds(pkey):
            raise PoolInvariantError(
                f"abort of rid {rid}: prefill role holds no pages")
        self._release(self.alloc, pkey)


# ---------------------------------------------------------------- scheduler
class Scheduler:
    """The admission/reaping/preemption policy extracted from the engine
    loops — behavior-identical, now one seam all engines route through
    (the RS103 lint accepts ``run`` bodies that call ``.validate``).

    Owns the queue and the rid -> current-Request map (a requeue swaps in
    the extended-prompt incarnation); the engines keep the device state
    and call back in for every policy decision.
    """

    def __init__(self, engine) -> None:
        self.engine = engine
        self.queue = RequestQueue()
        self.req_of: Dict[int, Request] = {}
        self.has_deadlines = False

    def validate(self, requests: Sequence[Request]
                 ) -> Tuple[List[Request], List[Request]]:
        """Admission-validate ``requests`` through the engine's
        ``admission_error`` hook (via ``_validate``) and seed the queue
        with the servable ones. Returns (servable, rejected)."""
        ok, rejected = self.engine._validate(requests)
        self.queue = RequestQueue(ok)
        self.req_of = {r.rid: r for r in ok}
        self.has_deadlines = any(r.deadline_s is not None for r in ok)
        return ok, rejected

    # ------------------------------------------------------------- queue
    def peek_best(self, now_rel: float) -> Optional[Request]:
        return self.queue.peek_best(now_rel)

    def take(self, req: Request) -> None:
        self.queue.remove(req)

    def requeue(self, req: Request) -> None:
        """Re-admit a preempted/faulted request (its prompt now carries
        any generated progress); it becomes the rid's current
        incarnation."""
        self.req_of[req.rid] = req
        self.queue.push(req)

    def next_arrival(self) -> float:
        return self.queue.next_arrival()

    def queue_depth(self) -> int:
        return len(self.queue)

    # ------------------------------------------------------------ reaping
    def reap_queued(self, now_rel: float) -> List[Request]:
        """Queued requests past their deadline (removed from the queue)."""
        if not self.has_deadlines:
            return []
        return self.queue.pop_expired(now_rel)

    def doomed_slots(self, now_rel: float, slot_rid: Sequence[Optional[int]],
                     active_host: np.ndarray) -> List[int]:
        """Active lanes whose request is past its deadline."""
        if not self.has_deadlines:
            return []
        return [int(s) for s in np.flatnonzero(active_host)
                if (d := self.req_of[slot_rid[s]].deadline_abs_s)
                is not None and now_rel > d]

    # --------------------------------------------------------- preemption
    def pick_victim(self, for_req: Request,
                    slot_rid: Sequence[Optional[int]],
                    active_host: np.ndarray,
                    admit_seq: Sequence[int]) -> Optional[int]:
        """Lane to evict for ``for_req``: the lowest-priority active
        request (ties: latest admitted — least sunk prefill), and only
        if it is *strictly* lower priority. ``None`` = don't preempt."""
        cands = [int(s) for s in np.flatnonzero(active_host)]
        if not cands:
            return None
        victim = min(cands, key=lambda s: (
            self.req_of[slot_rid[s]].priority, -admit_seq[s]))
        if self.req_of[slot_rid[victim]].priority >= for_req.priority:
            return None
        return victim

    # ---------------------------------------------------------- deadlines
    @staticmethod
    def deadline_truncate(t_first: float, step_times: Sequence[float],
                          deadline: Optional[float]
                          ) -> Tuple[int, float, bool]:
        """Credit tokens only up to the deadline — the uniform rule the
        per-step reapers already implement and the static engine now
        shares (it used to credit every generated token post hoc, so an
        expired request over-counted).

        ``t_first`` is when token 0 (the prefill token) was ready and
        ``step_times`` the durations of the decode steps that produced
        tokens 1..N. Returns ``(n_tokens, finish_s, timed_out)``; at
        least the prefill token is always counted (matching the per-step
        engines, which count the admission token before their reaper can
        fire)."""
        if deadline is None:
            return len(step_times) + 1, t_first + float(sum(step_times)), False
        n, t = 1, t_first
        for dt in step_times:
            if t + dt > deadline:
                break
            t += dt
            n += 1
        timed_out = (t_first > deadline) or (n < len(step_times) + 1)
        return n, t, timed_out


# ------------------------------------------------------------------ workers
class PrefillWorker:
    """The prefill role: page reservation (under the prefill owner key)
    and the chunked prefill dispatches, with a virtual timeline for
    disaggregated scheduling. Thin by design — compute stays on the
    engine's jitted entry points; the worker owns *whose clock the work
    bills to* and the role-local counters."""

    def __init__(self, engine, wid: int = 0) -> None:
        self.engine = engine
        self.wid = wid
        self.t = 0.0                 # virtual timeline (disaggregated)
        self.busy_s = 0.0
        self.dispatches = 0

    # ---- paged flavor (block-table chunked prefill)
    def reserve(self, req: Request, alloc: PageAllocator, radix):
        """Reserve ``req``'s pages under the *prefill* role key (prefix
        attach included); ``None`` when the pool cannot cover it yet."""
        return self.engine._reserve_pages(req, alloc, radix,
                                          owner=prefill_owner(req.rid))

    def prefill(self, prompt: np.ndarray, btab_dev, clock, *, rid: int,
                start: int = 0):
        """Chunk-prefill request ``rid``'s ``prompt[start:]`` into the
        reserved pages; returns (last chunk's logits, chunks
        dispatched)."""
        logits, chunks = self.engine._chunked_prefill(
            prompt, btab_dev, clock, rid=rid, start=start)
        self.dispatches += chunks
        return logits, chunks

    # ---- monolithic flavor (whole-batch prefill, static/continuous)
    def prefill_batch(self, prompts: np.ndarray, key):
        """One-shot batch prefill; returns (tok0 (b, 1), caches)."""
        self.dispatches += 1
        return self.engine._prefill_one_batch(prompts, key)


class DecodeWorker:
    """The decode role over a pool of ``lanes`` decode lanes: the fused
    pool-step device state (block-table flavored when ``npag_max`` is
    given), per-lane bookkeeping, a virtual timeline, and decode-stall
    samples.

    A *stall* is the gap between the end of one decode step and the
    start of the next while the worker still had active lanes — exactly
    the time interleaved engines spend on admission prefills between
    decode steps, the interference P/D disaggregation removes. The
    engine calls :meth:`note_step_start` / :meth:`note_step_end` with
    run-relative times (clock-based for interleaved, the worker
    timeline for disaggregated)."""

    def __init__(self, engine, lanes: int, wid: int = 0,
                 npag_max: Optional[int] = None) -> None:
        self.engine = engine
        self.wid = wid
        self.lanes = lanes
        T = engine.cache_span
        state = {
            "tok": jnp.zeros((lanes, 1), jnp.int32),
            "pos": jnp.zeros((lanes,), jnp.int32),
            "active": jnp.zeros((lanes,), bool),
            "ncount": jnp.zeros((lanes,), jnp.int32),
            "budget": jnp.ones((lanes,), jnp.int32),
            "tokbuf": jnp.zeros((lanes, T), jnp.int32),
        }
        if npag_max is not None:
            state["btab"] = jnp.zeros((lanes, npag_max), jnp.int32)
        self.state = state
        self.slot_rid: List[Optional[int]] = [None] * lanes
        self.admit_seq = [0] * lanes     # admission order, victim choice
        self.active_host = np.zeros(lanes, bool)
        self.slot_tokens = np.zeros(lanes, np.int64)
        self.t = 0.0                 # virtual timeline (disaggregated)
        self.busy_s = 0.0
        self.steps = 0
        self.stalls_s: List[float] = []
        self._prev_end = 0.0
        self._carry = False

    def free_lane(self) -> Optional[int]:
        free = np.flatnonzero(~self.active_host)
        return int(free[0]) if free.size else None

    # ---- stall accounting (run-relative times supplied by the engine)
    def note_step_start(self, now_rel: float) -> None:
        if self._carry:
            self.stalls_s.append(max(0.0, now_rel - self._prev_end))

    def note_step_end(self, now_rel: float) -> None:
        self._prev_end = now_rel
        self._carry = bool(self.active_host.any())

    # ---- fused device ops (paged pool-step signatures)
    def admit(self, tok0, btab_row, slot: int, plen: int, budget: int,
              active0: bool) -> None:
        self.state = self.engine._admit(self.state, tok0, btab_row, slot,
                                        plen, budget, active0)

    def evict(self, slot: int) -> None:
        self.state = self.engine._jit_evict(self.state, slot)

    def step(self, key):
        """One fused decode dispatch over this worker's lanes: runs the
        engine's pool step on the shared caches, blocks, charges the
        clock. Returns host copies of (new_active, ncounts)."""
        eng = self.engine
        with TraceAnnotation(N.DECODE, step=self.steps,
                             lanes=int(self.active_host.sum())):
            eng._caches, self.state = eng._pool_step(
                eng.params, eng._caches, self.state, key)
            jax.block_until_ready(self.state["active"])
            eng.clock.charge("decode")
            self.steps += 1
            with TraceAnnotation(N.LANE_STATE_READ):
                return (np.asarray(self.state["active"]),
                        np.asarray(self.state["ncount"]))


__all__ = [
    "DecodeWorker",
    "PageHandoff",
    "PrefillWorker",
    "RequestQueue",
    "Scheduler",
    "prefill_owner",
]
