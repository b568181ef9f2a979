"""Paged decode attention — Pallas TPU kernel over a block-table KV pool.

One decode token per sequence attends to its KV history stored in
scattered fixed-size pages of a global pool (see
:mod:`repro.serving.pages`). The physical pages are *gathered inside the
kernel*: the K/V pools stay in HBM, and the kernel copies each live page
it needs into VMEM itself, reading the page id from the per-sequence
block table, a scalar-prefetch SMEM operand — the pool never has to be
gathered into a contiguous activation on the host side.

The pool may be the model's whole stack of layers, ``(L, P, page_size,
Hkv, D)``, with the layer to read given as one more scalar-prefetch
operand: the layer scan then hands the kernel the pool it carries, and
no layer's pool is ever sliced out into an operand of its own. A
single-layer pool ``(P, page_size, Hkv, D)`` is the case ``L = 1``,
layer 0.

The walk is sized by live work. The grid is ``(B,)``, one step per row,
and a row's step loops over its live pages only: ``cdiv(length,
page_size)`` of them, or none for a retired row, which the engine marks
by an all-null block table (its first entry is the null page 0, which
the allocator never gives to a live row). A retired row fetches nothing,
computes nothing and writes zeros, whatever stale length it carries.

Tiling: the loop runs over blocks of ``pages_per_block`` whole pages,
each page ``(page_size, Hkv, D)`` of one layer moved by its own DMA into
one of two VMEM slots; block i+1's copies start before block i is
scored (double buffering). Only table entries below the row's page
count are copied; the unused page slots of a last, partial block are
masked out of both the scores and the values. ``pages_per_block`` trades
VMEM for fewer, larger DMA waves and matmuls. Like every other kernel,
``pages_per_block=None`` means "auto": resolved from the tuned-config
cache (:mod:`repro.kernels.tuning`, populated by ``python -m
benchmarks.run --tune``), else the default.
"""
from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import tuning

NEG_INF = -1e30


def _paged_kernel(btab_ref, len_ref, npages_ref, layer_ref, q_ref, k_hbm,
                  v_hbm, o_ref, k_buf, v_buf, sems, m_scr, l_scr, acc_scr,
                  *, scale: float, ps: int, ppb: int, hkv: int, g: int):
    """One row: the online softmax over its live pages, ``ppb`` at a time.

    k_hbm/v_hbm: the stacked pools (L, P, ps, Hkv, D), left in HBM;
    k_buf/v_buf: (2, ppb, ps, Hkv, D) VMEM slots; sems: (2, 2) DMA
    semaphores, [K or V, slot]. A block of pages is flattened to
    (ppb * ps * Hkv, D) rows, row c holding token c // Hkv of KV head
    c % Hkv, and all Hq query heads score against all rows in one matmul;
    a query head keeps only the rows of its own KV head. That spends Hkv
    times the minimum FLOPs on a loop that is bound by the pages' bytes,
    and needs no per-head slicing of the block."""
    b = pl.program_id(0)
    length = len_ref[b]
    n_pages = npages_ref[b]
    n_blocks = (n_pages + ppb - 1) // ppb
    layer = layer_ref[0]

    def dma(blk, slot, op):
        # start or wait for block ``blk``'s live pages in ``slot``; a
        # table entry at or past the row's page count is never read
        for p in range(ppb):
            @pl.when(blk * ppb + p < n_pages)
            def _page(p=p):
                page = btab_ref[b, blk * ppb + p]
                for side, (hbm, buf) in enumerate(((k_hbm, k_buf),
                                                   (v_hbm, v_buf))):
                    copy = pltpu.make_async_copy(
                        hbm.at[layer, page], buf.at[slot, p],
                        sems.at[side, slot])
                    if op == "start":
                        copy.start()
                    else:
                        copy.wait()

    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(n_blocks > 0)
    def _first():
        dma(0, 0, "start")

    hq = hkv * g
    D = q_ref.shape[-1]
    cols = ppb * ps * hkv
    q = q_ref[0].astype(jnp.float32) * scale                 # (Hq, D)
    row = jax.lax.broadcasted_iota(jnp.int32, (hq, cols), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (hq, cols), 1)
    own_head = (col % hkv) == (row // g)
    v_tok = jax.lax.broadcasted_iota(jnp.int32, (cols, 1), 0) // hkv

    def block(blk, carry):
        slot = blk % 2

        @pl.when(blk + 1 < n_blocks)
        def _prefetch():
            dma(blk + 1, 1 - slot, "start")

        dma(blk, slot, "wait")
        start = blk * ppb * ps                               # logical pos
        k = k_buf[slot].astype(jnp.float32).reshape(cols, D)
        v = v_buf[slot].astype(jnp.float32).reshape(cols, D)
        # unfetched slots and tokens past the length hold stale bytes
        v = jnp.where(start + v_tok < length, v, 0.0)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = jnp.where(own_head & (start + col // hkv < length), s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        pe = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + pe.sum(axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
            pe, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new
        return carry

    jax.lax.fori_loop(0, n_blocks, block, 0)
    # a row with no live pages has acc = l = 0 and writes zeros
    o_ref[0] = (acc_scr[...] /
                jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def paged_attention_fwd(q, k_pages, v_pages, block_tables, lengths,
                        layer=0, *, pages_per_block: int | None = None,
                        interpret: bool = False):
    """q: (B, 1, Hq, D); k_pages/v_pages: the stacked pools (L, P,
    page_size, Hkv, D), read at ``layer`` (a scalar, traced or not), or
    one layer's pool (P, page_size, Hkv, D) with ``layer`` 0;
    block_tables: (B, n_pages) int32 physical page ids (logical order,
    padded with the null page 0; a retired row is all null); lengths:
    (B,) int32 valid KV tokens. Returns (B, 1, Hq, D), zeros on retired
    rows. pages_per_block None = auto (tuned cache)."""
    B, one, Hq, D = q.shape
    if one != 1:
        raise ValueError(
            f"paged decode attention takes one query token per row, got "
            f"q.shape={q.shape}")
    if k_pages.ndim == 4:                     # one layer: L = 1, no copy
        k_pages, v_pages = k_pages[None], v_pages[None]
    ps, Hkv = k_pages.shape[2:4]
    npag = block_tables.shape[1]
    g = Hq // Hkv
    ppb = tuning.resolve_paged_pages_per_block(
        pages_per_block, q_shape=q.shape, pages_shape=k_pages.shape[1:],
        n_pages=npag, dtype=q.dtype)
    btab = jnp.asarray(block_tables, jnp.int32)
    lengths = jnp.asarray(lengths, jnp.int32).reshape(B)
    # live pages a row: none where the table is all null (a retired row)
    n_pages = jnp.where(btab[:, 0] == 0, 0,
                        jnp.minimum(-(-lengths // ps), npag))
    layer = jnp.asarray(layer, jnp.int32).reshape(1)

    def row_map(b, bt, ln, npg, ly):
        return (b, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(B,),
        in_specs=[pl.BlockSpec((1, Hq, D), row_map),
                  pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, Hq, D), row_map),
        scratch_shapes=[
            pltpu.VMEM((2, ppb, ps, Hkv, D), k_pages.dtype),
            pltpu.VMEM((2, ppb, ps, Hkv, D), v_pages.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.VMEM((Hq, 1), jnp.float32),
            pltpu.VMEM((Hq, 1), jnp.float32),
            pltpu.VMEM((Hq, D), jnp.float32),
        ],
    )
    kern = functools.partial(_paged_kernel, scale=1.0 / np.sqrt(D), ps=ps,
                             ppb=ppb, hkv=Hkv, g=g)
    out = pl.pallas_call(
        kern, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hq, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(btab, lengths, n_pages, layer, q.reshape(B, Hq, D), k_pages, v_pages)
    return out.reshape(B, 1, Hq, D)
