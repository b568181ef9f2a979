"""Paged KV-cache subsystem: the page allocator (free-list reuse,
fragmentation accounting, random admit/retire invariants), the Pallas
paged decode-attention kernel vs the gather reference, tuned tile-param
wiring, chunked-prefill equivalence, paged-vs-monolithic greedy token
parity across mixed prompt lengths, and the symmetric admission
validation shared by all three schedulers."""
from hypothesis import given, settings, strategies as st

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import ARCHS, MeshConfig, RunConfig, ShapeConfig, reduced
from repro.kernels import tuning
from repro.kernels.paged_attention import paged_attention_fwd
from repro.models.attention import paged_decode_attention_ref
from repro.runtime.steps import build_serve_steps
from repro.serving import (ContinuousEngine, PageAllocator, PagedEngine,
                           Request, SimClock, make_engine, pages_needed)

VOCAB = 17


# ------------------------------------------------------------- allocator
def test_pages_needed():
    assert pages_needed(0, 4) == 0
    assert pages_needed(1, 4) == 1
    assert pages_needed(4, 4) == 1
    assert pages_needed(5, 4) == 2


def test_allocator_free_list_reuse():
    """Freed pages go back on the free list and are reissued LIFO — the
    most recently retired request's pages come out first."""
    a = PageAllocator(num_pages=9, page_size=4)
    p1 = a.allocate(1, 10)                  # 3 pages
    p2 = a.allocate(2, 8)                   # 2 pages
    assert len(p1) == 3 and len(p2) == 2
    assert a.num_used == 5 and a.num_free == 3
    a.free(1)
    a.check()
    p3 = a.allocate(3, 12)                  # reuses rid 1's pages, LIFO
    assert p3 == p1[::-1]
    a.check()


def test_allocator_reserves_null_page():
    a = PageAllocator(num_pages=4, page_size=2)
    got = a.allocate(0, 6)                  # the whole usable pool
    assert 0 not in got and sorted(got) == [1, 2, 3]
    with pytest.raises(MemoryError):
        a.allocate(1, 1)
    assert a.failed_allocs == 1


def test_allocator_double_free_and_double_alloc():
    a = PageAllocator(num_pages=4, page_size=2)
    a.allocate(7, 2)
    with pytest.raises(ValueError, match="already holds"):
        a.allocate(7, 2)
    a.free(7)
    with pytest.raises(ValueError, match="double free"):
        a.free(7)


def test_allocator_fragmentation_accounting():
    a = PageAllocator(num_pages=9, page_size=4)
    a.allocate(1, 5)                        # 2 pages = 8 slots for 5 live
    assert a.fragmentation(5) == pytest.approx(3 / 8)
    assert a.fragmentation(8) == 0.0
    assert a.occupancy == pytest.approx(2 / 8)
    a.free(1)
    assert a.fragmentation(0) == 0.0        # empty pool: no fragmentation


@settings(max_examples=30, deadline=None)
@given(ops=st.lists(st.tuples(st.booleans(), st.integers(1, 40)),
                    min_size=1, max_size=60),
       page_size=st.sampled_from([1, 4, 16]))
def test_allocator_random_admit_retire(ops, page_size):
    """Random admit/retire sequences preserve the pool invariants: every
    usable page is free or owned exactly once, the null page is never
    issued, counts balance, and the high-water mark only grows."""
    a = PageAllocator(num_pages=17, page_size=page_size)
    live = []
    next_rid = 0
    hw = 0
    for admit, tokens in ops:
        if admit or not live:
            need = a.pages_needed(tokens)
            if need <= a.num_free:
                got = a.allocate(next_rid, tokens)
                assert len(got) == need
                live.append(next_rid)
                next_rid += 1
            else:
                with pytest.raises(MemoryError):
                    a.allocate(next_rid, tokens)
                next_rid += 1
        else:
            a.free(live.pop(0))
        assert a.num_used + a.num_free == a.usable_pages
        assert a.num_owners == len(live)
        assert 0.0 <= a.occupancy <= 1.0
        assert a.high_water >= hw
        hw = a.high_water
        a.check()
    for rid in live:
        a.free(rid)
    assert a.num_free == a.usable_pages and a.num_used == 0
    a.check()


# ---------------------------------------------------------------- kernel
def _kernel_inputs(seed, *, B, Hq, Hkv, D, L, P, ps, npag):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((B, 1, Hq, D)), jnp.float32)
    kp = jnp.asarray(rng.standard_normal((L, P, ps, Hkv, D)), jnp.float32)
    vp = jnp.asarray(rng.standard_normal((L, P, ps, Hkv, D)), jnp.float32)
    bt = jnp.asarray(rng.integers(1, P, size=(B, npag)), jnp.int32)
    return q, kp, vp, bt


@pytest.mark.parametrize("ppb", [1, 2, 3, 4, 8])
def test_paged_kernel_matches_reference(ppb):
    """The in-kernel block-table gather must match the gather-then-
    decode_attention reference for every pages_per_block tiling,
    including ones that do not divide the table width (a last, partial
    block), on the stacked pool at a nonzero layer, for lengths of one
    token, mid-page, on a page edge and the full table."""
    npag, ps = 10, 4
    q, kp, vp, bt = _kernel_inputs(0, B=4, Hq=4, Hkv=2, D=16, L=3, P=23,
                                   ps=ps, npag=npag)
    lens = jnp.asarray([1, 6, 8, npag * ps], jnp.int32)
    layer = jnp.int32(2)
    ref = paged_decode_attention_ref(q, kp, vp, bt, lens, layer)
    out = paged_attention_fwd(q, kp, vp, bt, lens, layer,
                              pages_per_block=ppb, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=1e-4)


@pytest.mark.parametrize("g", [1, 2, 4])
def test_paged_kernel_group_sizes(g):
    """Each query head of a group of g scores only its own KV head's
    rows of the flattened page block: MHA (g = 1) and GQA alike."""
    Hkv = 2
    q, kp, vp, bt = _kernel_inputs(5, B=3, Hq=g * Hkv, Hkv=Hkv, D=16, L=2,
                                   P=13, ps=4, npag=5)
    lens = jnp.asarray([3, 12, 20], jnp.int32)
    ref = paged_decode_attention_ref(q, kp, vp, bt, lens, jnp.int32(1))
    out = paged_attention_fwd(q, kp, vp, bt, lens, jnp.int32(1),
                              pages_per_block=3, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=1e-4)


@pytest.mark.parametrize("ppb", [2, 3])
def test_paged_kernel_reads_only_live_pages(ppb):
    """A row reads only its live pages, cdiv(length, page_size) of them,
    and a retired row (its table all null) reads nothing and scores
    nothing, whatever stale length it carries.

    Every page a live row's table names past its live pages, the null
    page and the tokens past each live row's length hold NaN: live rows
    still equal the reference on the clean pool, and retired rows are
    zero. Run by the TPU interpreter, which raises on a read outside a
    buffer: page ids outside the pool past the live pages, and in a
    retired row's table, change nothing, while one among a row's live
    pages is read and refused."""
    from jax.experimental.pallas import tpu as pltpu

    B, ps, npag, P, layer = 6, 4, 5, 24, jnp.int32(1)
    q, kp, vp, _ = _kernel_inputs(11, B=B, Hq=4, Hkv=2, D=16, L=2, P=P,
                                  ps=ps, npag=npag)
    lens = np.asarray([1, 6, 8, 20, 13, 20], np.int32)
    live = lens.copy()
    live[4:] = 0                                   # rows 4, 5 retired
    n_live = -(-live // ps)
    bt = np.zeros((B, npag), np.int32)
    bt[:4] = np.arange(1, 4 * npag + 1).reshape(4, npag)   # distinct pages
    ref = paged_decode_attention_ref(q, kp, vp, jnp.asarray(bt),
                                     jnp.asarray(lens), layer)
    kpn, vpn = np.array(kp), np.array(vp)
    for b in range(4):
        for pool in (kpn, vpn):
            pool[:, bt[b, n_live[b]:]] = np.nan
            last = bt[b, n_live[b] - 1]
            pool[:, last, lens[b] - (n_live[b] - 1) * ps:] = np.nan
    kpn[:, 0] = vpn[:, 0] = np.nan                 # the null page

    def run(table, lengths=lens):
        return np.asarray(paged_attention_fwd(
            q, jnp.asarray(kpn), jnp.asarray(vpn), jnp.asarray(table),
            jnp.asarray(lengths), layer, pages_per_block=ppb,
            interpret=pltpu.InterpretParams()))

    out = run(bt)
    assert np.isfinite(out).all()
    np.testing.assert_allclose(out[:4], np.asarray(ref)[:4],
                               atol=2e-5, rtol=1e-4)
    np.testing.assert_array_equal(out[4:], 0.0)
    outside = bt.copy()
    for b in range(B):
        first = max(n_live[b], 1)
        outside[b, first:] = P + 100 + np.arange(npag - first)
    np.testing.assert_array_equal(run(outside), out)
    longer = lens.copy()
    longer[0] = 2 * ps                             # page 1 of row 0: live
    with pytest.raises(Exception):
        run(outside, longer)


def test_paged_tuning_wiring():
    """None = auto resolves through DEFAULTS; explicit values win; the
    ops wrapper accepts the auto path."""
    from repro.bench.tune import paged_candidates
    from repro.kernels import ops

    sig_args = dict(q_shape=(2, 1, 4, 16), pages_shape=(8, 4, 2, 16),
                    n_pages=16, dtype=np.float32)
    assert tuning.resolve_paged_pages_per_block(None, **sig_args) == \
        tuning.DEFAULTS["paged_attention_fwd"]["pages_per_block"] == 8
    assert tuning.resolve_paged_pages_per_block(4, **sig_args) == 4
    cands, rejected, default = paged_candidates(
        n_pages=64, ps=16, hkv=2, g=2, D=64, itemsize=4)
    assert default == {"pages_per_block": 8} and cands[0] == default
    assert {c["pages_per_block"] for c in cands} == {4, 8, 16, 32}
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.standard_normal((2, 1, 4, 16)), jnp.float32)
    kp = jnp.asarray(rng.standard_normal((8, 4, 2, 16)), jnp.float32)
    bt = jnp.asarray(rng.integers(1, 8, (2, 4)), jnp.int32)
    out = ops.paged_decode_attention(q, kp, kp, bt,
                                     jnp.asarray([3, 9], jnp.int32))
    assert out.shape == (2, 1, 4, 16)


@pytest.mark.parametrize("ppb", [1, 2])
@pytest.mark.parametrize("layer", [0, 1, 2])
def test_paged_kernel_reads_stacked_pool_at_layer(layer, ppb):
    """On the stacked (L, P, ps, Hkv, D) pool the kernel reads the layer
    its scalar operand names: it equals the reference on that layer's
    pool alone, for lengths that end mid-page and on a page boundary."""
    rng = np.random.default_rng(7)
    B, Hq, Hkv, D, L, P, ps, npag = 4, 4, 2, 16, 3, 11, 4, 5
    q = jnp.asarray(rng.standard_normal((B, 1, Hq, D)), jnp.float32)
    kp = jnp.asarray(rng.standard_normal((L, P, ps, Hkv, D)), jnp.float32)
    vp = jnp.asarray(rng.standard_normal((L, P, ps, Hkv, D)), jnp.float32)
    bt = jnp.asarray(rng.integers(1, P, size=(B, npag)), jnp.int32)
    lens = jnp.asarray([6, 8, 1, 20], jnp.int32)     # mid, edge, 1, full
    ref = paged_decode_attention_ref(q, kp[layer], vp[layer], bt, lens)
    out = paged_attention_fwd(q, kp, vp, bt, lens, jnp.int32(layer),
                              pages_per_block=ppb, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=1e-4)
    stacked_ref = paged_decode_attention_ref(q, kp, vp, bt, lens,
                                             jnp.int32(layer))
    np.testing.assert_array_equal(np.asarray(stacked_ref), np.asarray(ref))


def test_paged_kernel_single_layer_pool_is_layer_zero():
    """A 4-D pool is the stacked pool with L = 1 read at layer 0."""
    from repro.kernels import ops

    rng = np.random.default_rng(8)
    q = jnp.asarray(rng.standard_normal((2, 1, 4, 16)), jnp.float32)
    kp = jnp.asarray(rng.standard_normal((9, 4, 2, 16)), jnp.float32)
    vp = jnp.asarray(rng.standard_normal((9, 4, 2, 16)), jnp.float32)
    bt = jnp.asarray(rng.integers(1, 9, (2, 3)), jnp.int32)
    lens = jnp.asarray([5, 12], jnp.int32)
    flat = ops.paged_decode_attention(q, kp, vp, bt, lens)
    stacked = ops.paged_decode_attention(q, kp[None], vp[None], bt, lens,
                                         jnp.int32(0))
    np.testing.assert_array_equal(np.asarray(flat), np.asarray(stacked))


# ------------------------------------------------- model-level paged path
def _tiny_serve(arch="granite-3-8b", span=24, slots=2):
    cfg = reduced(ARCHS[arch], layers=2, d_model=64, vocab=128, d_ff=128)
    rcfg = RunConfig(model=cfg, shape=ShapeConfig("s", "decode", span, slots),
                     mesh=MeshConfig(shape=(1, 1), axes=("data", "model")),
                     attention_backend="dense", param_dtype="float32",
                     decode_attention="simple")
    prefill_fn, decode_fn, model = build_serve_steps(rcfg)
    params = model.init_params(jax.random.PRNGKey(0))
    return cfg, prefill_fn, decode_fn, model, params


def test_chunked_prefill_matches_one_shot():
    """Feeding the prompt in chunks through the paged pools must produce
    the same next-token logits as the one-shot monolithic prefill, for
    several chunk sizes including non-dividing ones."""
    span, ps = 24, 4
    cfg, prefill_fn, _, model, params = _tiny_serve(span=span)
    rng = np.random.default_rng(3)
    prompt = rng.integers(1, cfg.vocab_size, size=9).astype(np.int32)
    ref_logits, _ = prefill_fn(params, {"tokens": jnp.asarray(prompt[None])},
                               span)
    btab = jnp.arange(1, 7, dtype=jnp.int32)[None]      # 6 pages = span
    for chunk in (3, 4, 9):
        caches = model.paged_cache_init(8, ps)
        for start in range(0, len(prompt), chunk):
            toks = jnp.asarray(prompt[None, start:start + chunk])
            logits, caches = model.prefill_chunk(params, caches, toks,
                                                 btab, jnp.int32(start))
        np.testing.assert_allclose(np.asarray(logits),
                                   np.asarray(ref_logits),
                                   atol=1e-4, rtol=1e-4)


def test_paged_decode_matches_monolithic_decode():
    """Greedy decode through the paged pool emits exactly the tokens the
    monolithic cache path emits."""
    span, ps, steps = 24, 4, 5
    cfg, prefill_fn, decode_fn, model, params = _tiny_serve(span=span)
    rng = np.random.default_rng(0)
    prompt = rng.integers(1, cfg.vocab_size, size=7).astype(np.int32)

    logits, caches = prefill_fn(params, {"tokens": jnp.asarray(prompt[None])},
                                span)
    tok = jnp.argmax(logits[:, -1:], -1).astype(jnp.int32)
    ref = [int(tok[0, 0])]
    for i in range(steps - 1):
        logits, caches = decode_fn(params, caches, tok,
                                   jnp.int32(len(prompt) + i))
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        ref.append(int(tok[0, 0]))

    pcaches = model.paged_cache_init(8, ps)
    btab = jnp.arange(1, 7, dtype=jnp.int32)[None]
    lg, pcaches = model.prefill_chunk(params, pcaches,
                                      jnp.asarray(prompt[None]), btab,
                                      jnp.int32(0))
    tok = jnp.argmax(lg[:, -1:], -1).astype(jnp.int32)
    got = [int(tok[0, 0])]
    pos = jnp.asarray([len(prompt)], jnp.int32)
    for i in range(steps - 1):
        lg, pcaches = model.decode_step_paged(params, pcaches, tok, pos + i,
                                              btab)
        tok = jnp.argmax(lg, -1).astype(jnp.int32)
        got.append(int(tok[0, 0]))
    assert got == ref


def test_paged_cache_init_rejects_unsupported_families():
    cfg, *_ = _tiny_serve(arch="rwkv6-3b")
    from repro.models import transformer as tfm
    with pytest.raises(ValueError, match="full-attention"):
        tfm.paged_cache_init(cfg, 2, 8, 4, jnp.float32)


# The paged layer scan as it was before the stacked pools moved into the
# scan's carry: each layer's pool sliced out as scan xs, updated, and
# stacked back as ys. The carried scan must match it exactly.
def _old_layer_decode_paged(p, x, cache, pos, block_tables, cfg, rt):
    from repro.models import attention as attn_mod
    from repro.models import transformer as tfm
    from repro.models.layers import apply_norm

    h_in = apply_norm(p["norm1"], x, cfg.norm)
    q, k, v = attn_mod.project_qkv(p["attn"], h_in, h_in, cfg)
    q, k = tfm._rope_q_k(cfg, q, k, pos.reshape(-1, 1))
    ps = cache["k"].shape[1]
    bidx = jnp.arange(x.shape[0])
    pages = block_tables[bidx, pos // ps]
    k_pool = cache["k"].at[pages, pos % ps].set(k[:, 0])
    v_pool = cache["v"].at[pages, pos % ps].set(v[:, 0])
    if rt.attention_backend == "pallas":
        from repro.kernels import ops
        o = ops.paged_decode_attention(q, k_pool, v_pool, block_tables,
                                       pos + 1)
    else:
        o = paged_decode_attention_ref(q, k_pool, v_pool, block_tables,
                                       pos + 1)
    x = x + o.reshape(*x.shape[:-1], -1) @ p["attn"]["wo"]
    h, _ = tfm._ffn(p, apply_norm(p["norm2"], x, cfg.norm), cfg, rt)
    return x + h, {"k": k_pool, "v": v_pool}


def _old_layer_prefill_chunk(p, x, cache, block_tables, positions, cfg, rt):
    from repro.models import attention as attn_mod
    from repro.models import transformer as tfm
    from repro.models.layers import apply_norm

    h_in = apply_norm(p["norm1"], x, cfg.norm)
    q, k, v = attn_mod.project_qkv(p["attn"], h_in, h_in, cfg)
    q, k = tfm._rope_q_k(cfg, q, k, positions[None])
    B, C = x.shape[:2]
    ps = cache["k"].shape[1]
    npag = block_tables.shape[1]
    pages = jnp.take(block_tables, positions // ps, axis=1)
    offs = jnp.broadcast_to((positions % ps)[None], (B, C))
    k_pool = cache["k"].at[pages, offs].set(k)
    v_pool = cache["v"].at[pages, offs].set(v)
    k_all = k_pool[block_tables].reshape(B, npag * ps, *k.shape[2:])
    v_all = v_pool[block_tables].reshape(B, npag * ps, *v.shape[2:])
    o = attn_mod.dense_attention(q, k_all, v_all, causal=True,
                                 q_offset=positions[0])
    x = x + o.reshape(*x.shape[:-1], -1) @ p["attn"]["wo"]
    h, _ = tfm._ffn(p, apply_norm(p["norm2"], x, cfg.norm), cfg, rt)
    return x + h, {"k": k_pool, "v": v_pool}


def _old_stack_decode_paged(stacked, x, caches, pos, block_tables, cfg, rt):
    def body(carry, xs):
        p_layer, cache = xs
        return _old_layer_decode_paged(p_layer, carry, cache, pos,
                                       block_tables, cfg, rt)
    return jax.lax.scan(body, x, (stacked, caches))


def _old_stack_prefill_chunk(stacked, x, caches, block_tables, positions,
                             cfg, rt):
    def body(carry, xs):
        p_layer, cache = xs
        return _old_layer_prefill_chunk(p_layer, carry, cache, block_tables,
                                        positions, cfg, rt)
    return jax.lax.scan(body, x, (stacked, caches))


def _serve_steps(model, params, pools, prompts, btab, chunk, steps):
    """Two rows: a chunked prefill of both prompts, then greedy decode
    steps. Returns every step's logits and the final pools."""
    out = []
    plen = prompts.shape[1]
    for start in range(0, plen, chunk):
        lg, pools = model.prefill_chunk(
            params, pools, jnp.asarray(prompts[:, start:start + chunk]),
            btab, jnp.int32(start))
        out.append(lg)
    tok = jnp.argmax(lg[:, -1:], -1).astype(jnp.int32)
    pos = jnp.asarray([plen, plen - 2], jnp.int32)
    for i in range(steps):
        lg, pools = model.decode_step_paged(params, pools, tok, pos + i,
                                            btab)
        out.append(lg)
        tok = jnp.argmax(lg, -1).astype(jnp.int32)
    return out, pools


@pytest.mark.parametrize("backend", ["dense", "pallas"])
def test_carried_pool_scan_matches_sliced_scan(backend, monkeypatch):
    """Chunked prefill and paged decode with the stacked pools in the
    layer scan's carry give the very logits and pools of the sliced
    xs/ys scan, on the reference and the (interpret-mode) Pallas paths,
    over two chunks and several decode steps."""
    from repro.models import transformer as tfm
    from repro.models.model import build

    cfg = reduced(ARCHS["granite-3-8b"], layers=3, d_model=64, vocab=128,
                  d_ff=128)
    model = build(cfg, tfm.Runtime(attention_backend=backend),
                  param_dtype=jnp.float32)
    params = model.init_params(jax.random.PRNGKey(0))
    rng = np.random.default_rng(4)
    # a pool whose other pages hold noise: a read of a wrong layer or page
    # would show in the logits
    pools = jax.tree.map(
        lambda a: jnp.asarray(rng.standard_normal(a.shape), a.dtype),
        model.paged_cache_init(10, 4))
    prompts = rng.integers(1, cfg.vocab_size, size=(2, 7)).astype(np.int32)
    btab = jnp.asarray([[3, 7, 1], [9, 2, 5]], jnp.int32)
    new_out, new_pools = _serve_steps(model, params, pools, prompts, btab,
                                      chunk=4, steps=3)
    monkeypatch.setattr(tfm, "stack_decode_paged", _old_stack_decode_paged)
    monkeypatch.setattr(tfm, "stack_prefill_chunk", _old_stack_prefill_chunk)
    old_out, old_pools = _serve_steps(model, params, pools, prompts, btab,
                                      chunk=4, steps=3)
    assert len(new_out) == 5
    for a, b in zip(new_out, old_out):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for side in ("k", "v"):
        np.testing.assert_array_equal(np.asarray(new_pools["layers"][side]),
                                      np.asarray(old_pools["layers"][side]))


# ------------------------------------------------------- paged engine
def test_paged_engine_parity_mixed_prompt_lengths():
    """PagedEngine greedy streams are token-identical to the monolithic
    ContinuousEngine across mixed prompt lengths — including a request
    admitted into reused pages after a retirement."""
    span = 24
    cfg, prefill_fn, decode_fn, model, params = _tiny_serve(span=span)
    rng = np.random.default_rng(0)
    pA = rng.integers(1, cfg.vocab_size, size=5).astype(np.int32)
    pB = rng.integers(1, cfg.vocab_size, size=9).astype(np.int32)
    reqs = lambda: [Request(0, pA, 5), Request(1, pB, 5),
                    Request(2, pA, 5)]
    mono = ContinuousEngine(prefill_fn, decode_fn, params, model.cache_init,
                            slots=2, cache_span=span, clock=SimClock())
    paged = PagedEngine(model.prefill_chunk, model.decode_step_paged,
                        params, model.paged_cache_init, slots=2,
                        cache_span=span, page_size=4,
                        prefill_chunk_tokens=4, clock=SimClock())
    got_m = [list(m.tokens) for m in mono.run(reqs()).metrics]
    rep_p = paged.run(reqs())
    got_p = [list(m.tokens) for m in rep_p.metrics]
    assert got_m == got_p
    assert rep_p.completed == 3
    assert rep_p.page_occupancy_peak > 0
    assert 0.0 <= rep_p.fragmentation_mean < 1.0


def test_paged_engine_pallas_matches_reference_backend():
    """The paged engine on the (interpret-mode) Pallas kernel emits the
    very token streams of the gather reference, with lanes retiring
    mid-run: a retired lane keeps its stale position and an all-null
    table, and later requests reuse lanes and pages."""
    from repro.models import transformer as tfm
    from repro.models.model import build

    cfg = reduced(ARCHS["granite-3-8b"], layers=2, d_model=64, vocab=128,
                  d_ff=128)
    rng = np.random.default_rng(6)
    prompts = [rng.integers(1, cfg.vocab_size, size=n).astype(np.int32)
               for n in (9, 3, 6, 11, 5)]
    budgets = (2, 7, 3, 5, 6)
    streams = []
    for backend in ("dense", "pallas"):
        model = build(cfg, tfm.Runtime(attention_backend=backend,
                                       paged_pages_per_block=2),
                      param_dtype=jnp.float32)
        params = model.init_params(jax.random.PRNGKey(2))
        eng = PagedEngine(model.prefill_chunk, model.decode_step_paged,
                          params, model.paged_cache_init, slots=3,
                          cache_span=24, page_size=4,
                          prefill_chunk_tokens=4, clock=SimClock())
        rep = eng.run([Request(i, p, n) for i, (p, n)
                       in enumerate(zip(prompts, budgets))])
        assert rep.completed == len(prompts)
        streams.append([list(m.tokens) for m in rep.metrics])
    assert [len(t) for t in streams[1]] == list(budgets)
    assert streams[1] == streams[0]


# ------------------------------------------ stub engines (scheduling only)
def stub_prefill(params, batch, cache_span):
    B = batch["tokens"].shape[0]
    logits = jnp.zeros((B, 1, VOCAB)).at[:, :, 1].set(100.0)
    return logits, {"k": jnp.zeros((1, B, cache_span, 2))}


def stub_decode(params, caches, tok, pos):
    pos_v = jnp.broadcast_to(jnp.atleast_1d(pos), (tok.shape[0],))
    lg = jax.nn.one_hot(jnp.minimum(pos_v + 1, VOCAB - 1), VOCAB) * 100.0
    return lg[:, None, :], caches


def stub_cache_init(batch, max_len, dtype=jnp.float32):
    return {"k": jnp.zeros((1, batch, max_len, 2), dtype)}


def stub_chunk_prefill(params, caches, tokens, block_tables, start_pos):
    """Paged-signature twin of stub_prefill: same spike-at-1 logits."""
    B = tokens.shape[0]
    logits = jnp.zeros((B, 1, VOCAB)).at[:, :, 1].set(100.0)
    return logits, caches


def stub_paged_decode(params, caches, tok, pos, block_tables):
    return stub_decode(params, caches, tok, pos)


def stub_paged_cache_init(num_pages, page_size, dtype=jnp.float32):
    return {"k": jnp.zeros((1, num_pages, page_size, 2), dtype)}


def _paged_stub_engine(**kw):
    kw.setdefault("clock", SimClock())
    return PagedEngine(stub_chunk_prefill, stub_paged_decode, None,
                       stub_paged_cache_init, **kw)


def test_paged_engine_admits_more_at_equal_budget():
    """Equal KV budget (2 slots x 16-token span = 32 tokens): the
    monolithic engine caps at 2 concurrent requests; the paged pool
    (32 tokens = 8 pages of 4, null page included) fits 3 short
    requests at once."""
    span, n = 16, 6
    reqs = lambda: [Request(i, np.full(4, 2, np.int32), 2)
                    for i in range(n)]
    mono = ContinuousEngine(stub_prefill, stub_decode, None,
                            stub_cache_init, slots=2, cache_span=span,
                            clock=SimClock())
    rep_m = mono.run(reqs())
    paged = _paged_stub_engine(slots=4, cache_span=span, page_size=4,
                               num_pages=2 * span // 4)
    rep_p = paged.run(reqs())
    assert rep_m.completed == rep_p.completed == n
    assert rep_p.peak_concurrency > rep_m.peak_concurrency
    assert rep_p.peak_concurrency == 3      # ceil(6/4)=2 pages x 3 <= 7


def test_paged_engine_blocks_admission_until_pages_free():
    """A request that fits the pool but not the current free list waits
    at the queue head and is admitted after a retirement frees pages —
    counted in admission_blocked_steps."""
    eng = _paged_stub_engine(slots=2, cache_span=16, page_size=4,
                             num_pages=4)      # 3 usable pages
    reqs = [Request(0, np.full(4, 2, np.int32), 6),    # 10 tok = 3 pages
            Request(1, np.full(4, 2, np.int32), 6)]
    rep = eng.run(reqs)
    assert rep.completed == 2
    assert rep.admission_blocked_steps > 0
    assert rep.peak_concurrency == 1
    m0, m1 = rep.metrics
    assert m1.admitted_s >= m0.finish_s     # strictly after retirement
    np.testing.assert_array_equal(m0.tokens, m1.tokens)


def test_paged_engine_token_streams_and_page_reuse():
    """5 requests through 2 lanes and a small pool: every request
    completes with the position-correct stream, pages are recycled."""
    eng = _paged_stub_engine(slots=2, cache_span=16, page_size=4,
                             num_pages=6)
    reqs = [Request(i, np.full(4, 2, np.int32), 4) for i in range(5)]
    rep = eng.run(reqs)
    assert rep.completed == 5
    for m in rep.metrics:
        np.testing.assert_array_equal(m.tokens, [1, 5, 6, 7])
    assert rep.page_occupancy_peak <= 1.0
    s = rep.summary()
    assert s["num_pages"] == 6 and s["page_size"] == 4


def test_paged_engine_donates_pool_and_state():
    """The fused pool step consumes its cache and lane-state buffers on
    every backend, the CPU included, so a stale read fails in tests as
    it would on the chip."""
    from repro.serving.roles import DecodeWorker

    eng = _paged_stub_engine(slots=2, cache_span=16, page_size=4,
                             num_pages=6)
    caches = eng.cache_init(eng.num_pages, eng.page_size)
    state = DecodeWorker(eng, 2, npag_max=eng.npag_max).state
    new_caches, new_state = eng._pool_step(None, caches, state,
                                           jax.random.PRNGKey(0))
    assert caches["k"].is_deleted() and state["tokbuf"].is_deleted()
    assert not new_caches["k"].is_deleted()
    assert not new_state["tokbuf"].is_deleted()


# --------------------------------------------------- symmetric validation
def _make(scheduler, **kw):
    if scheduler == "paged":
        return _paged_stub_engine(**kw)
    return make_engine(scheduler, stub_prefill, stub_decode, None,
                       stub_cache_init, clock=SimClock(), **kw)


@pytest.mark.parametrize("scheduler", ["static", "continuous", "paged"])
def test_admission_validation_symmetric(scheduler):
    """All three engines route rejection through the same validated
    hook: identical errors for a zero budget and for a request
    exceeding the span — no scheduler silently admits what another
    rejects (the continuous/paged paths used to diverge from the
    static one)."""
    eng = _make(scheduler, slots=1, cache_span=8)
    with pytest.raises(ValueError,
                       match="max_new_tokens must be >= 1, got 0"):
        eng.run([Request(0, np.full(4, 2, np.int32), max_new_tokens=0)])
    with pytest.raises(ValueError, match="exceeds cache_span 8"):
        eng.run([Request(0, np.full(4, 2, np.int32), max_new_tokens=5)])
    assert eng.admission_error(
        Request(0, np.full(4, 2, np.int32), max_new_tokens=4)) is None


def test_paged_rejects_over_pool_capacity():
    """The paged engine's admission check speaks pages: a request that
    can never fit the pool is rejected up front with the shared
    validated error, not left to deadlock the queue."""
    eng = _paged_stub_engine(slots=1, cache_span=32, page_size=4,
                             num_pages=4)      # 3 usable = 12 tokens
    with pytest.raises(ValueError, match="usable pages"):
        eng.run([Request(0, np.full(8, 2, np.int32), max_new_tokens=8)])
    # same request against a big-enough pool is admissible
    ok = _paged_stub_engine(slots=1, cache_span=32, page_size=4,
                            num_pages=8)
    assert ok.admission_error(
        Request(0, np.full(8, 2, np.int32), max_new_tokens=8)) is None


def test_make_engine_builds_paged():
    eng = make_engine("paged", stub_chunk_prefill, stub_paged_decode, None,
                      stub_paged_cache_init, slots=2, cache_span=16,
                      page_size=4, clock=SimClock())
    assert isinstance(eng, PagedEngine)
    rep = eng.run([Request(0, np.full(4, 2, np.int32), 3)])
    np.testing.assert_array_equal(rep.metrics[0].tokens, [1, 5, 6])
