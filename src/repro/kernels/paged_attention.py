"""Paged decode attention — Pallas TPU kernel over a block-table KV pool.

One decode token per sequence attends to its KV history stored in
scattered fixed-size pages of a global pool (see
:mod:`repro.serving.pages`). The physical pages are *gathered inside the
kernel*: the per-sequence block table rides in as a scalar-prefetch SMEM
operand, and each K/V BlockSpec's ``index_map`` reads the table to pick
the physical page its DMA fetches — the pool never has to be gathered
into a contiguous activation on the host side.

The pool may be the model's whole stack of layers, ``(L, P, page_size,
Hkv, D)``, with the layer to read given as one more scalar-prefetch
operand: the layer scan then hands the kernel the pool it carries, and
no layer's pool is ever sliced out into an operand of its own. A
single-layer pool ``(P, page_size, Hkv, D)`` is the case ``L = 1``,
layer 0.

Tiling: grid ``(B, n_blocks)`` with the page-block dim innermost and
sequential ("arbitrary"), so the online-softmax accumulators live in
VMEM scratch across page blocks. Each K/V block is one whole page with
all its KV heads, ``(page_size, Hkv, D)`` of one layer, so one DMA moves
a page.
The tunable tile parameter is ``pages_per_block``: how many pages one
grid step consumes. It is realised by passing the pool
``pages_per_block`` times with offset index maps — each copy is an
independent page DMA the pipeline keeps in flight, so larger values
trade VMEM for fewer grid steps. Like every
other kernel, ``pages_per_block=None`` means "auto": resolved from the
tuned-config cache (:mod:`repro.kernels.tuning`, populated by
``python -m benchmarks.run --tune``), default 1.

Pages logically past a sequence's length are skipped with ``pl.when``;
their block-table entries point at the reserved null page (id 0) so even
the skipped DMAs touch valid memory.
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


def _paged_kernel(btab_ref, len_ref, layer_ref, q_ref, *refs, scale: float,
                  ps: int, ppb: int, nb: int, hkv: int, g: int):
    """refs = k_ref x ppb, v_ref x ppb, o_ref, m_scr, l_scr, acc_scr.
    ``layer_ref`` is read only by the K/V index maps.

    A K/V block is one whole page, every KV head: (1, ps, Hkv, D). It is
    flattened to (ps * Hkv, D) rows, row c holding token c // Hkv of KV
    head c % Hkv, and all Hq query heads score against all rows in one
    matmul; a query head keeps only the rows of its own KV head. That
    spends Hkv times the minimum FLOPs on a step that is bound by the
    page's bytes, and needs no per-head slicing of the block."""
    k_refs = refs[:ppb]
    v_refs = refs[ppb:2 * ppb]
    o_ref, m_scr, l_scr, acc_scr = refs[2 * ppb:]
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    length = len_ref[b]
    hq = hkv * g
    q = q_ref[0].astype(jnp.float32) * scale                 # (Hq, D)
    row = jax.lax.broadcasted_iota(jnp.int32, (hq, ps * hkv), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (hq, ps * hkv), 1)
    own_head = (col % hkv) == (row // g)
    for p in range(ppb):
        page_start = (j * ppb + p) * ps                      # logical pos

        def _consume(p=p, page_start=page_start):
            D = q.shape[-1]
            k = k_refs[p][0].astype(jnp.float32).reshape(ps * hkv, D)
            v = v_refs[p][0].astype(jnp.float32).reshape(ps * hkv, D)
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            kpos = page_start + col // hkv
            s = jnp.where(own_head & (kpos < length), s, NEG_INF)
            m_prev = m_scr[...]
            m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
            pe = jnp.exp(s - m_new)
            corr = jnp.exp(m_prev - m_new)
            l_scr[...] = l_scr[...] * corr + pe.sum(axis=1, keepdims=True)
            acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
                pe, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_scr[...] = m_new

        pl.when(page_start < length)(_consume)

    @pl.when(j == nb - 1)
    def _finish():
        o_ref[0] = (acc_scr[...] /
                    jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def paged_attention_fwd(q, k_pages, v_pages, block_tables, lengths,
                        layer=0, *, pages_per_block: int | None = None,
                        interpret: bool = False):
    """q: (B, 1, Hq, D); k_pages/v_pages: the stacked pools (L, P,
    page_size, Hkv, D), read at ``layer`` (a scalar, traced or not), or
    one layer's pool (P, page_size, Hkv, D) with ``layer`` 0;
    block_tables: (B, n_pages) int32 physical page ids (logical order,
    padded with the null page 0); lengths: (B,) int32 valid KV tokens.
    Returns (B, 1, Hq, D). pages_per_block None = auto (tuned cache)."""
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
    nb = -(-npag // ppb)                      # grid steps over page blocks
    pad = nb * ppb - npag
    btab = jnp.asarray(block_tables, jnp.int32)
    if pad:
        btab = jnp.pad(btab, ((0, 0), (0, pad)))      # null-page padding
    lengths = jnp.asarray(lengths, jnp.int32).reshape(B)
    layer = jnp.asarray(layer, jnp.int32).reshape(1)

    def q_map(b, j, bt, ln, ly):
        return (b, 0, 0)

    def kv_map(p):
        # the in-kernel gather: physical page id straight from the table,
        # in the layer the scalar operand names
        def index_map(b, j, bt, ln, ly, p=p):
            return (ly[0], bt[b, j * ppb + p], 0, 0, 0)
        return index_map

    # whole pages, all KV heads, of one layer: the last two block dims
    # equal the pool's (Hkv, D), which Mosaic accepts for any head count
    kv_spec = [pl.BlockSpec((None, 1, ps, Hkv, D), kv_map(p))
               for p in range(ppb)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, nb),
        in_specs=[pl.BlockSpec((1, Hq, D), q_map), *kv_spec, *kv_spec],
        out_specs=pl.BlockSpec((1, Hq, D), q_map),
        scratch_shapes=[
            pltpu.VMEM((Hq, 1), jnp.float32),
            pltpu.VMEM((Hq, 1), jnp.float32),
            pltpu.VMEM((Hq, D), jnp.float32),
        ],
    )
    kern = functools.partial(_paged_kernel, scale=1.0 / np.sqrt(D), ps=ps,
                             ppb=ppb, nb=nb, hkv=Hkv, g=g)
    out = pl.pallas_call(
        kern, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hq, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(btab, lengths, layer, q.reshape(B, Hq, D), *([k_pages] * ppb),
      *([v_pages] * ppb))
    return out.reshape(B, 1, Hq, D)
