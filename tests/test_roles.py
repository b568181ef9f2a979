"""Composable serving roles (repro.serving.roles) and the P/D
disaggregated engine: PageHandoff ownership invariants (never dual-held,
refcount-conserving, PoolInvariantError on protocol violations), the
extracted Scheduler's reaping/preemption/deadline-truncation policy, and
disaggregated-vs-interleaved greedy token parity on stub engines."""
from hypothesis import given, settings, strategies as st

import numpy as np
import pytest

from repro.serving import (DisaggregatedEngine, PageAllocator, PageHandoff,
                           PoolInvariantError, Request, Scheduler, SimClock,
                           prefill_owner)
from test_paged import (_paged_stub_engine, stub_chunk_prefill,
                        stub_paged_cache_init, stub_paged_decode)


def _release(alloc, key):
    """Stands in for the engine's bound ``_release_pages`` seam."""
    alloc.free(key)


def _handoff(num_pages=17, page_size=4):
    alloc = PageAllocator(num_pages=num_pages, page_size=page_size)
    return alloc, PageHandoff(alloc, _release, page_size)


def _disagg_stub_engine(**kw):
    kw.setdefault("clock", SimClock())
    return DisaggregatedEngine(stub_chunk_prefill, stub_paged_decode, None,
                               stub_paged_cache_init, **kw)


# ------------------------------------------------------------- handoff
def test_transfer_moves_ownership_and_conserves_refcounts():
    alloc, h = _handoff()
    pages = alloc.allocate(prefill_owner(5), 10)      # 3 pages
    assert h.roles_of(5) == (True, False)
    used_before = alloc.num_used
    got = h.transfer(5)
    assert got == pages
    assert h.roles_of(5) == (False, True)
    assert alloc.owned(5) == pages
    assert alloc.num_used == used_before              # net-zero refcounts
    assert h.handoffs == 1
    alloc.check()


def test_double_handoff_raises():
    alloc, h = _handoff()
    alloc.allocate(prefill_owner(5), 6)
    h.transfer(5)
    alloc.allocate(prefill_owner(5), 6)   # prefill re-reserves the rid
    with pytest.raises(PoolInvariantError, match="double handoff"):
        h.transfer(5)


def test_transfer_without_reservation_raises():
    _, h = _handoff()
    with pytest.raises(PoolInvariantError,
                       match="handoff without reservation"):
        h.transfer(9)


def test_abort_releases_prefill_hold():
    alloc, h = _handoff()
    alloc.allocate(prefill_owner(3), 8)
    h.abort(3)
    assert h.roles_of(3) == (False, False)
    assert alloc.num_owners == 0
    with pytest.raises(PoolInvariantError, match="holds no pages"):
        h.abort(3)
    alloc.check()


@settings(max_examples=40, deadline=None)
@given(ops=st.lists(st.tuples(st.integers(0, 3), st.integers(1, 20)),
                    min_size=1, max_size=50))
def test_handoff_roles_never_overlap(ops):
    """Random grant/transfer/retire/abort sequences preserve the handoff
    invariants: a request's pages are never held by both roles at once,
    every op leaves the pool check()-clean, and draining both roles
    returns the pool to empty (refcounts conserved end to end)."""
    alloc, h = _handoff(num_pages=33)
    prefill_held, decode_held = set(), set()
    rid = 0
    for op, tokens in ops:
        if op == 0:                      # prefill reserves a new request
            if alloc.pages_needed(tokens) <= alloc.num_free:
                alloc.allocate(prefill_owner(rid), tokens)
                prefill_held.add(rid)
                rid += 1
        elif op == 1 and prefill_held:   # handoff to decode
            r = min(prefill_held)
            assert h.transfer(r)
            prefill_held.discard(r)
            decode_held.add(r)
        elif op == 2 and decode_held:    # decode retires
            r = min(decode_held)
            _release(alloc, r)
            decode_held.discard(r)
        elif op == 3 and prefill_held:   # prefill aborts
            r = max(prefill_held)
            h.abort(r)
            prefill_held.discard(r)
        for r in prefill_held | decode_held:
            pheld, dheld = h.roles_of(r)
            assert pheld == (r in prefill_held)
            assert dheld == (r in decode_held)
            assert not (pheld and dheld)
        assert alloc.num_owners == len(prefill_held) + len(decode_held)
        alloc.check()
    for r in sorted(prefill_held):
        h.abort(r)
    for r in sorted(decode_held):
        _release(alloc, r)
    assert alloc.num_owners == 0 and alloc.num_used == 0
    alloc.check()


# ----------------------------------------------------------- scheduler
def _req(rid, budget=2, **kw):
    return Request(rid, np.full(4, 2, np.int32), budget, **kw)


def test_scheduler_validate_seeds_queue_and_reaps_expired():
    eng = _paged_stub_engine(slots=2, cache_span=16, page_size=4,
                             num_pages=16)
    sched = Scheduler(eng)
    ok, rejected = sched.validate([_req(0, deadline_s=5.0),
                                   _req(1, arrival_s=1.0)])
    assert [r.rid for r in ok] == [0, 1] and not rejected
    assert sched.queue_depth() == 2 and sched.has_deadlines
    assert sched.reap_queued(3.0) == []          # not expired yet
    reaped = sched.reap_queued(20.0)
    assert [r.rid for r in reaped] == [0]
    assert sched.queue_depth() == 1


def test_pick_victim_lowest_priority_newest_strictly_below():
    eng = _paged_stub_engine(slots=3, cache_span=16, page_size=4,
                             num_pages=16)
    sched = Scheduler(eng)
    sched.validate([_req(0, priority=0), _req(1, priority=0),
                    _req(2, priority=5)])
    slot_rid = [0, 1, 2]
    active = np.array([True, True, True])
    admit_seq = [1, 2, 3]
    high = _req(9, priority=3)
    # both prio-0 lanes qualify; the later-admitted one (least sunk
    # prefill) is the victim
    assert sched.pick_victim(high, slot_rid, active, admit_seq) == 1
    equal = _req(10, priority=0)
    assert sched.pick_victim(equal, slot_rid, active, admit_seq) is None
    assert sched.pick_victim(high, slot_rid,
                             np.zeros(3, bool), admit_seq) is None


def test_deadline_truncate_no_deadline_counts_everything():
    n, t, out = Scheduler.deadline_truncate(10.0, [1.0] * 7, None)
    assert (n, t, out) == (8, 17.0, False)


def test_deadline_truncate_credits_only_pre_deadline_tokens():
    """The static-engine over-count case: first token at t=10, seven
    1s decode steps, deadline 12 — only tokens landing by the deadline
    (prefill + 2 decode) are credited, and the request times out."""
    n, t, out = Scheduler.deadline_truncate(10.0, [1.0] * 7, 12.0)
    assert (n, t, out) == (3, 12.0, True)


def test_deadline_truncate_late_first_token_keeps_one():
    n, t, out = Scheduler.deadline_truncate(10.0, [1.0] * 4, 5.0)
    assert (n, t, out) == (1, 10.0, True)


def test_deadline_truncate_exact_boundary_counts():
    # landing exactly on the deadline is a make (reapers use strict >)
    n, t, out = Scheduler.deadline_truncate(1.0, [1.0, 1.0], 3.0)
    assert (n, t, out) == (3, 3.0, False)


# -------------------------------------------------- disaggregated engine
def test_disagg_worker_count_validation():
    with pytest.raises(ValueError, match="divide evenly"):
        _disagg_stub_engine(slots=4, cache_span=16, page_size=4,
                            num_pages=16, decode_workers=3)
    with pytest.raises(ValueError, match=">= 1 worker"):
        _disagg_stub_engine(slots=4, cache_span=16, page_size=4,
                            num_pages=16, prefill_workers=0)


def test_disagg_token_parity_with_interleaved_stub():
    """Greedy tokens per request are identical between the interleaved
    paged loop and the disaggregated worker pools on a staggered
    stream, with exactly one handoff per request and no leaked pages."""
    span, n = 16, 6

    def reqs():
        return [_req(i, budget=3, arrival_s=0.5 * i) for i in range(n)]

    paged = _paged_stub_engine(slots=4, cache_span=span, page_size=4,
                               num_pages=16)
    disagg = _disagg_stub_engine(slots=4, cache_span=span, page_size=4,
                                 num_pages=16, prefill_workers=2,
                                 decode_workers=2)
    rp, rd = paged.run(reqs()), disagg.run(reqs())
    assert rp.completed == rd.completed == n
    toks_p = {m.rid: list(m.tokens) for m in rp.metrics}
    toks_d = {m.rid: list(m.tokens) for m in rd.metrics}
    assert toks_d == toks_p
    assert rd.handoffs == n
    assert rd.pages_leaked == 0
    assert rd.prefill_workers == 2 and rd.decode_workers == 2


def test_disagg_metrics_carry_role_assignments():
    disagg = _disagg_stub_engine(slots=2, cache_span=16, page_size=4,
                                 num_pages=16, decode_workers=2)
    rep = disagg.run([_req(i, budget=3) for i in range(4)])
    assert rep.completed == 4
    for m in rep.metrics:
        assert m.prefill_worker == 0          # single prefill worker
        assert m.decode_worker in (0, 1)
        assert m.handoff_latency_s >= 0.0
    assert len(rep.handoff_latencies_s) == rep.handoffs == 4
    s = rep.summary()
    assert 0.0 < s["prefill_util"] <= 1.0
    assert 0.0 < s["decode_util"] <= 1.0
    assert s["queue_depth_peak"] >= 1


def test_disagg_reaps_deadlines_per_role():
    """A queued request whose deadline passes before any prefill worker
    reaches it is reaped (timed_out) without ever holding pages."""
    disagg = _disagg_stub_engine(slots=1, cache_span=32, page_size=4,
                                 num_pages=16)
    rep = disagg.run([_req(0, budget=8, deadline_s=500.0),
                      _req(1, budget=8, deadline_s=15.0)])
    by_rid = {m.rid: m for m in rep.metrics}
    assert by_rid[0].outcome == "completed"
    # r1's deadline (15s) expires during r0's prefill+decode (SimClock:
    # 10s prefill + 8x1s decode), before the lone lane frees up
    assert by_rid[1].outcome == "timed_out"
    assert rep.pages_leaked == 0
