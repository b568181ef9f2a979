"""Prefix-sharing subsystem: refcounted allocate/share/release/free
invariants (hypothesis), invariant checks that survive ``python -O``,
the radix cache's longest-prefix/insert/evict properties, the
multi-turn session workload, and end-to-end engine behavior — CoW
parity (shared-prefix decode greedy-token-identical to cold prefill),
byte-identical disabled-cache output, and warm-vs-cold TTFT."""
import os
import subprocess
import sys
from pathlib import Path

from hypothesis import given, settings, strategies as st

import numpy as np
import pytest

from repro.configs import get_arch
from repro.data.pipeline import synth_sessions
from repro.serving import (PageAllocator, PagedEngine, PoolInvariantError,
                           RadixCache, Request, SimClock)

from test_paged import (_paged_stub_engine, _tiny_serve)


# ------------------------------------------------- refcounted allocator
def test_allocate_with_shared_pages():
    a = PageAllocator(num_pages=9, page_size=4)
    p1 = a.allocate(1, 16)                      # 4 pages, refcount 1
    a.share(p1[:2])                             # cache holds the prefix
    p2 = a.allocate(2, 16, shared=p1[:2])       # 2 shared + 2 fresh
    assert p2[:2] == p1[:2] and len(p2) == 4
    assert a.refcount(p1[0]) == 3               # owner 1, owner 2, cache
    assert a.num_free == 9 - 1 - 6              # 6 distinct pages in use
    a.check()
    # owner 1 retires: shared pages stay resident, its tail pages free
    freed = a.free(1)
    assert set(freed) == set(p1[2:])
    assert a.refcount(p1[0]) == 2
    # owner 2 retires: prefix survives on the cache's reference alone
    freed = a.free(2)
    assert set(freed) == set(p2[2:])
    assert a.refcount(p1[0]) == 1
    a.check()
    # the cache lets go: now the prefix pages actually free
    assert set(a.release(p1[:2])) == set(p1[:2])
    assert a.num_free == a.usable_pages
    a.check()


def test_allocate_shared_validation():
    a = PageAllocator(num_pages=9, page_size=4)
    with pytest.raises(ValueError, match="not issued"):
        a.allocate(1, 8, shared=[3])
    p1 = a.allocate(1, 8)
    with pytest.raises(ValueError, match="exceed"):
        a.allocate(2, 4, shared=p1)             # 2 shared > 1 page needed
    with pytest.raises(ValueError, match="not issued"):
        a.share([8])
    with pytest.raises(ValueError, match="not issued"):
        a.release([8])


def test_can_fit_counts_shared_pages():
    a = PageAllocator(num_pages=5, page_size=4)
    p1 = a.allocate(1, 16)                      # whole pool
    assert not a.can_fit(16)
    assert a.can_fit(16, shared_pages=4)        # fully cached: 0 fresh
    a.free(1)
    assert a.can_fit(16)
    assert p1


@settings(max_examples=30, deadline=None)
@given(ops=st.lists(st.tuples(st.integers(0, 3), st.integers(1, 40)),
                    min_size=1, max_size=60),
       page_size=st.sampled_from([1, 4, 16]))
def test_refcount_random_share_release(ops, page_size):
    """Random allocate-with-sharing / share / release / free sequences
    preserve the refcount invariants: a page is never freed while its
    refcount is positive, free+used always partition the pool (counting
    distinct pages), and when every owner retires and the cache drops
    its holds, nothing leaks."""
    a = PageAllocator(num_pages=17, page_size=page_size)
    live = []                  # owners
    cache_held = []            # ownerless references, LIFO
    next_rid = 0
    for op, tokens in ops:
        if op == 0 or not live:               # allocate, maybe sharing
            donor = a.owned(live[-1]) if live else []
            need = a.pages_needed(tokens)
            shared = donor[:min(len(donor), need)]
            if need - len(shared) <= a.num_free:
                got = a.allocate(next_rid, tokens, shared=shared)
                assert got[:len(shared)] == shared
                for p in shared:
                    assert a.refcount(p) >= 2
                live.append(next_rid)
            else:
                with pytest.raises(MemoryError):
                    a.allocate(next_rid, tokens, shared=shared)
            next_rid += 1
        elif op == 1:                          # cache takes a reference
            pages = a.owned(live[0])
            a.share(pages)
            cache_held.append(pages)
        elif op == 2 and cache_held:           # cache drops a reference
            a.release(cache_held.pop())
        else:                                  # an owner retires
            rid = live.pop(0)
            held = a.owned(rid)
            before = {p: a.refcount(p) for p in held}
            freed = a.free(rid)
            for p in held:
                if before[p] > 1:              # still referenced: kept
                    assert p not in freed
                    assert a.refcount(p) == before[p] - 1
                else:
                    assert p in freed and a.refcount(p) == 0
        a.check()
    for rid in live:
        a.free(rid)
    for pages in cache_held:
        a.release(pages)
    assert a.num_free == a.usable_pages and a.num_used == 0
    a.check()


# ---------------------------------------------- check() under python -O
def test_pool_invariant_error_is_assertion_error():
    assert issubclass(PoolInvariantError, AssertionError)


def test_check_raises_on_corruption():
    a = PageAllocator(num_pages=5, page_size=4)
    a.allocate(1, 8)
    a._free.append(a._owned[1][0])             # corrupt: issued AND free
    with pytest.raises(PoolInvariantError, match="issued and free"):
        a.check()


def test_check_raises_under_disabled_asserts():
    """The invariant checks must stay live under ``python -O`` — a bare
    ``assert`` would be compiled away and corruption would pass
    silently. Run a corrupted pool through check() in a -O subprocess
    and require the explicit PoolInvariantError."""
    src = Path(__file__).resolve().parents[1] / "src"
    prog = (
        "import sys; assert not __debug__, 'run me with -O'\n"
        "from repro.serving import PageAllocator, PoolInvariantError\n"
        "a = PageAllocator(num_pages=5, page_size=4)\n"
        "a.allocate(1, 8)\n"
        "a._free.append(a._owned[1][0])\n"
        "try:\n"
        "    a.check()\n"
        "except PoolInvariantError:\n"
        "    sys.exit(0)\n"
        "sys.exit(1)\n"
    )
    res = subprocess.run([sys.executable, "-O", "-c", prog],
                         capture_output=True, text=True,
                         env={**os.environ, "PYTHONPATH": str(src)})
    assert res.returncode == 0, (res.stdout, res.stderr)


# ------------------------------------------------------------ radix cache
def _cache(num_pages=33, page_size=4):
    a = PageAllocator(num_pages=num_pages, page_size=page_size)
    return RadixCache(a), a


def test_radix_lookup_empty():
    c, _ = _cache()
    assert c.lookup(np.arange(1, 9)) == ([], 0)


def test_radix_insert_then_longest_prefix():
    c, a = _cache(page_size=4)
    seq = np.asarray([1, 2, 3, 4, 5, 6, 7, 8, 9, 10], np.int32)
    pages = a.allocate(0, len(seq))
    added = c.insert(seq, pages)
    assert added == 2                          # only the 2 full pages
    assert a.refcount(pages[0]) == 2           # owner + cache
    assert a.refcount(pages[2]) == 1           # partial page: not indexed
    # full match of both indexed pages
    got, n = c.lookup(np.asarray([1, 2, 3, 4, 5, 6, 7, 8, 42]))
    assert got == pages[:2] and n == 8
    # diverges inside page 2: only page 1 matches
    got, n = c.lookup(np.asarray([1, 2, 3, 4, 5, 0, 0, 0]))
    assert got == pages[:1] and n == 4
    # shorter than one page: no match
    assert c.lookup(np.asarray([1, 2, 3])) == ([], 0)


def test_radix_insert_existing_keeps_first_writer():
    c, a = _cache(page_size=4)
    s = np.asarray([1, 2, 3, 4], np.int32)
    p1 = a.allocate(0, 4)
    p2 = a.allocate(1, 4)
    assert c.insert(s, p1) == 1
    assert c.insert(s, p2) == 0                # duplicate content: kept
    assert c.lookup(s)[0] == p1
    assert a.refcount(p2[0]) == 1              # no extra cache reference


@settings(max_examples=25, deadline=None)
@given(lengths=st.lists(st.integers(1, 24), min_size=1, max_size=6),
       probe_len=st.integers(0, 30),
       page_size=st.sampled_from([2, 4]))
def test_radix_longest_prefix_property(lengths, probe_len, page_size):
    """Against a brute-force reference: after inserting arbitrary
    sequences drawn from a tiny alphabet (maximizing shared prefixes),
    lookup(probe) matches exactly the longest inserted page-aligned
    prefix of the probe."""
    rng = np.random.default_rng(sum(lengths) * 31 + probe_len)
    c, a = _cache(num_pages=257, page_size=page_size)
    inserted = set()                           # indexed chunk paths
    for i, n in enumerate(lengths):
        seq = rng.integers(1, 3, n).astype(np.int32)
        pages = a.allocate(i, max(n, 1))
        c.insert(seq, pages)
        full = (n // page_size) * page_size
        for k in range(page_size, full + 1, page_size):
            inserted.add(tuple(seq[:k]))
    probe = rng.integers(1, 3, probe_len).astype(np.int32)
    want = 0
    full = (probe_len // page_size) * page_size
    for k in range(page_size, full + 1, page_size):
        if tuple(probe[:k]) in inserted:
            want = k
        else:
            break
    pages, n = c.lookup(probe)
    assert n == want
    assert len(pages) == want // page_size


def test_radix_evicts_lru_refcount_one_only():
    c, a = _cache(num_pages=5, page_size=4)
    p1 = a.allocate(0, 8)
    c.insert(np.asarray([1, 2, 3, 4, 5, 6, 7, 8]), p1)
    a.free(0)                                  # cache is now sole holder
    p2 = a.allocate(1, 8)
    c.insert(np.asarray([9, 9, 9, 9, 8, 8, 8, 8]), p2)  # owner 1 lives
    assert a.num_free == 0
    # only p1's leaf is refcount-1; deeper p1 node frees on a second pass
    freed = c.evict(2)
    assert freed == 2 and a.num_free == 2 and c.evictions == 2
    assert c.lookup(np.asarray([1, 2, 3, 4]))[1] == 0
    # p2's nodes are pinned by owner 1's references
    assert c.evict(1) == 0
    assert c.lookup(np.asarray([9, 9, 9, 9]))[1] == 4
    a.check()


def test_radix_evict_respects_protect():
    c, a = _cache(num_pages=5, page_size=4)
    p1 = a.allocate(0, 8)
    c.insert(np.asarray([1, 2, 3, 4, 5, 6, 7, 8]), p1)
    a.free(0)
    assert c.evict(2, protect=frozenset(p1)) == 0
    assert c.lookup(np.asarray([1, 2, 3, 4, 5, 6, 7, 8]))[1] == 8
    assert c.evict(2) == 2                     # unprotected: both go


# -------------------------------------------------- session workload
def test_synth_sessions_replay_structure():
    cfg = get_arch("granite-3-8b")
    reqs = synth_sessions(cfg, 3, 4, system_len=8, turn_len=4,
                          think_s=5.0, stagger_s=20.0, seed=11)
    assert len(reqs) == 12
    assert [r.arrival_s for r in reqs] == sorted(r.arrival_s for r in reqs)
    by_session = {}
    for r in reqs:
        by_session.setdefault(r.rid // 100, []).append(r)
    system = reqs[0].prompt[:8]
    for sid, turns in by_session.items():
        turns.sort(key=lambda r: r.rid)
        for t, r in enumerate(turns):
            assert r.rid == sid * 100 + t
            assert r.prompt_len == 8 + 4 * (t + 1)
            np.testing.assert_array_equal(r.prompt[:8], system)
            if t:    # each turn extends the previous turn's prompt
                prev = turns[t - 1].prompt
                np.testing.assert_array_equal(r.prompt[:len(prev)], prev)
                assert r.arrival_s == turns[t - 1].arrival_s + 5.0
    # deterministic in the seed
    again = synth_sessions(cfg, 3, 4, system_len=8, turn_len=4,
                           think_s=5.0, stagger_s=20.0, seed=11)
    for r, s in zip(reqs, again):
        np.testing.assert_array_equal(r.prompt, s.prompt)
        assert (r.rid, r.arrival_s) == (s.rid, s.arrival_s)


# ------------------------------------------------------ engine behavior
def _shared_burst_reqs(n=6, budget=4):
    rng = np.random.default_rng(0)
    system = rng.integers(1, 100, 16).astype(np.int32)
    out = []
    for i in range(n):
        sfx = rng.integers(1, 100, 4).astype(np.int32)
        out.append(Request(rid=i, prompt=np.concatenate([system, sfx]),
                           max_new_tokens=budget))
    return out


def test_disabled_cache_is_byte_identical():
    """--no-prefix-cache must leave the paged engine's report exactly as
    the pre-cache scheduler produced it (satellite guarantee: enabling
    the feature flag off changes nothing)."""
    kw = dict(slots=4, cache_span=24, page_size=4, num_pages=25,
              prefill_chunk_tokens=8)
    base = _paged_stub_engine(**kw, clock=SimClock())
    off = _paged_stub_engine(**kw, prefix_cache=False, clock=SimClock())
    reqs = _shared_burst_reqs
    ra, rb = base.run(reqs()), off.run(reqs())
    assert not ra.prefix_enabled and not rb.prefix_enabled
    assert ra.summary() == rb.summary()
    for ma, mb in zip(ra.metrics, rb.metrics):
        np.testing.assert_array_equal(ma.tokens, mb.tokens)
        assert (ma.ttft_s, ma.finish_s, ma.slot) == (
            mb.ttft_s, mb.finish_s, mb.slot)


def test_prefix_cache_stub_shares_and_saves():
    kw = dict(slots=4, cache_span=24, page_size=4, num_pages=25,
              prefill_chunk_tokens=8)
    off = _paged_stub_engine(**kw, clock=SimClock())
    on = _paged_stub_engine(**kw, prefix_cache=True, clock=SimClock())
    ra, rb = off.run(_shared_burst_reqs()), on.run(_shared_burst_reqs())
    assert rb.prefix_hits > 0 and rb.prefill_tokens_saved > 0
    assert rb.pages_shared_peak > 0
    assert rb.prefix_hit_rate == rb.prefix_hits / rb.prefix_lookups
    for ma, mb in zip(ra.metrics, rb.metrics):
        np.testing.assert_array_equal(ma.tokens, mb.tokens)


def test_cow_parity_real_model():
    """Greedy decode from a shared cached prefix — including the
    copy-on-write path when the whole prompt is cached — emits exactly
    the tokens a cold prefill emits."""
    span = 24
    cfg, _, _, model, params = _tiny_serve(span=span)
    rng = np.random.default_rng(1)
    shared = rng.integers(1, cfg.vocab_size, 8).astype(np.int32)
    branch = np.concatenate(
        [shared, rng.integers(1, cfg.vocab_size, 3).astype(np.int32)])
    # rid 1 re-sends rid 0's exact prompt (whole-prompt CoW), rid 2
    # extends the shared prefix (page-aligned warm suffix), rid 3 hits
    # with a 1-token budget (finishes at admission)
    reqs = lambda: [Request(0, shared.copy(), 5, 0.0),
                    Request(1, shared.copy(), 5, 30.0),
                    Request(2, branch.copy(), 5, 60.0),
                    Request(3, shared.copy(), 1, 90.0)]
    runs = {}
    for pc in (False, True):
        eng = PagedEngine(model.prefill_chunk, model.decode_step_paged,
                          params, model.paged_cache_init, slots=2,
                          cache_span=span, page_size=4,
                          prefill_chunk_tokens=4, clock=SimClock(),
                          prefix_cache=pc)
        runs[pc] = eng.run(reqs())
    toks = {pc: [list(m.tokens) for m in r.metrics]
            for pc, r in runs.items()}
    assert toks[True] == toks[False]
    on = runs[True]
    cached = {m.rid: m.cached_prompt_tokens for m in on.metrics}
    assert cached[0] == 0                      # cold: nothing indexed yet
    assert cached[1] == 7                      # whole prompt cached, CoW
    assert cached[2] == 8                      # aligned warm suffix
    assert on.prefill_tokens_saved == sum(cached.values())
    assert on.ttft_warm_samples_s() and on.ttft_cold_samples_s()


def test_multi_turn_replay_warm_beats_cold():
    """Session replay through the stub engine: every turn after the
    first is warm, and on a SimClock warm TTFT is strictly below cold
    TTFT (fewer prefill chunks)."""
    cfg = get_arch("granite-3-8b")
    reqs = synth_sessions(cfg, 2, 3, system_len=8, turn_len=4,
                          max_new_tokens=2, think_s=100.0,
                          stagger_s=40.0, seed=5)
    eng = _paged_stub_engine(slots=4, cache_span=24, page_size=4,
                             num_pages=40, prefill_chunk_tokens=4,
                             prefix_cache=True, clock=SimClock())
    rep = eng.run(reqs)
    assert rep.completed == len(reqs)
    warm, cold = rep.ttft_warm_samples_s(), rep.ttft_cold_samples_s()
    assert warm and cold
    assert max(warm) < min(cold)
    assert rep.prefix_hit_rate > 0.5
