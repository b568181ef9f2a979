"""Causal/sliding GQA flash attention — Pallas TPU kernel.

Tiling: grid (B, Hq, Sq/bq, Sk/bk); the kv-block dim is the innermost
SEQUENTIAL ("arbitrary") dim so the online-softmax accumulators live in
VMEM scratch across kv blocks. Block shapes are MXU-aligned (bq, bk
multiples of 128 when the sequence allows; head_dim padded to 128 lanes by
Mosaic). GQA is handled in the kv index_map (hq -> hq // group).

Fully-masked kv blocks are skipped with pl.when (forward AND backward,
including the sliding-window bound), so the causal lower triangle
intersected with the window band is the only work executed — matching the
chunked-jnp stand-in the dry-run compiles and the flop accounting in
§Roofline.

Tile sizes: ``block_q``/``block_k`` default to ``None`` ("auto") and
resolve through the tuned-config cache (:mod:`repro.kernels.tuning`,
populated by ``python -m benchmarks.run --tune``), falling back to the
historical 128/128 constants on a cache miss.
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


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                 scale: float, causal: bool, window: int,
                 bq: int, bk: int, nk: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_lo = iq * bq
    k_lo = ik * bk
    # block is live unless fully masked out
    live = True
    if causal:
        live = k_lo <= q_lo + bq - 1
    if window:
        live = jnp.logical_and(live, k_lo + bk - 1 >= q_lo - window + 1) \
            if causal else live

    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale          # (bq, D)
        k = k_ref[0, 0].astype(jnp.float32)                  # (bk, D)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        qpos = q_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = k_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = jnp.ones((bq, bk), jnp.bool_)
        if causal:
            mask = jnp.logical_and(mask, qpos >= kpos)
        if window:
            mask = jnp.logical_and(mask, qpos - kpos < window)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + p.sum(axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    if isinstance(live, bool):
        _compute()
    else:
        pl.when(live)(_compute)

    @pl.when(ik == nk - 1)
    def _finish():
        o_ref[0, 0] = (acc_scr[...] /
                       jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def _attn_kernel_lse(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr,
                     acc_scr, *, scale, causal, window, bq, bk, nk):
    """Forward that also emits logsumexp rows (needed by the backward)."""
    _attn_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
                 scale=scale, causal=causal, window=window, bq=bq, bk=bk,
                 nk=nk)
    ik = pl.program_id(3)

    @pl.when(ik == nk - 1)
    def _emit_lse():
        lse_ref[0, 0] = m_scr[...] + jnp.log(jnp.maximum(l_scr[...], 1e-30))


def flash_attention_fwd(q, k, v, *, causal: bool = True, window: int = 0,
                        block_q: int | None = None,
                        block_k: int | None = None,
                        interpret: bool = False, return_lse: bool = False):
    """q: (B, Sq, Hq, D); k/v: (B, Sk, Hkv, D) -> (B, Sq, Hq, D)
    [, lse (B, Hq, Sq, 1)]. block_q/block_k None = auto (tuned cache).

    The logsumexp rows keep a trailing unit dim so their block
    ``(1, 1, bq, 1)`` meets Mosaic's rule for the last two block dims
    (multiples of (8, 128) or equal to the array's own dims)."""
    B, Sq, Hq, D = q.shape
    _, Sk, Hkv, _ = k.shape
    block_q, block_k = tuning.resolve_attention_blocks(
        block_q, block_k, q_shape=q.shape, k_shape=k.shape, dtype=q.dtype,
        causal=causal, window=window, kernel="flash_attention_fwd")
    g = Hq // Hkv
    bq = min(block_q, Sq)
    bk = min(block_k, Sk)
    if Sq % bq or Sk % bk:
        raise ValueError(
            f"attention blocks must tile the sequence: Sq={Sq} bq={bq} "
            f"Sk={Sk} bk={bk}")
    nq, nk = Sq // bq, Sk // bk
    scale = 1.0 / np.sqrt(D)
    qt = q.transpose(0, 2, 1, 3)      # (B, Hq, Sq, D)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)

    kw = dict(scale=scale, causal=causal, window=window, bq=bq, bk=bk, nk=nk)
    in_specs = [
        pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
        pl.BlockSpec((1, 1, bk, D), lambda b, h, i, j: (b, h // g, j, 0)),
        pl.BlockSpec((1, 1, bk, D), lambda b, h, i, j: (b, h // g, j, 0)),
    ]
    scratch = [
        pltpu.VMEM((bq, 1), jnp.float32),
        pltpu.VMEM((bq, 1), jnp.float32),
        pltpu.VMEM((bq, D), jnp.float32),
    ]
    params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"))
    o_spec = pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0))
    if not return_lse:
        out = pl.pallas_call(
            functools.partial(_attn_kernel, **kw),
            grid=(B, Hq, nq, nk), in_specs=in_specs, out_specs=o_spec,
            out_shape=jax.ShapeDtypeStruct((B, Hq, Sq, D), q.dtype),
            scratch_shapes=scratch, compiler_params=params,
            interpret=interpret,
        )(qt, kt, vt)
        return out.transpose(0, 2, 1, 3)
    lse_spec = pl.BlockSpec((1, 1, bq, 1), lambda b, h, i, j: (b, h, i, 0))
    out, lse = pl.pallas_call(
        functools.partial(_attn_kernel_lse, **kw),
        grid=(B, Hq, nq, nk), in_specs=in_specs,
        out_specs=[o_spec, lse_spec],
        out_shape=[jax.ShapeDtypeStruct((B, Hq, Sq, D), q.dtype),
                   jax.ShapeDtypeStruct((B, Hq, Sq, 1), jnp.float32)],
        scratch_shapes=scratch, compiler_params=params,
        interpret=interpret,
    )(qt, kt, vt)
    return out.transpose(0, 2, 1, 3), lse


# ------------------------------------------------------------------ backward
def _block_mask_iota(q_lo, k_lo, bq, bk, causal, window):
    qpos = q_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kpos = k_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = jnp.ones((bq, bk), jnp.bool_)
    if causal:
        mask = jnp.logical_and(mask, qpos >= kpos)
    if window:
        mask = jnp.logical_and(mask, qpos - kpos < window)
    return mask


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               acc_scr, *, scale, causal, window, bq, bk, nk):
    iq, ik = pl.program_id(2), pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_lo, k_lo = iq * bq, ik * bk
    live = (k_lo <= q_lo + bq - 1) if causal else True
    if window and causal:
        # sliding window: blocks entirely left of the band are dead too
        live = jnp.logical_and(live, k_lo + bk - 1 >= q_lo - window + 1)

    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0]                                  # (bq, 1)
        delta = delta_ref[0, 0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        mask = _block_mask_iota(q_lo, k_lo, bq, bk, causal, window)
        p = jnp.where(mask, jnp.exp(s - lse), 0.0)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        acc_scr[...] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale

    if isinstance(live, bool):
        _compute()
    else:
        pl.when(live)(_compute)

    @pl.when(ik == nk - 1)
    def _finish():
        dq_ref[0, 0] = acc_scr[...].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_scr, dv_scr, *, scale, causal, window,
                bq, bk, nq):
    ik, iq = pl.program_id(2), pl.program_id(3)

    @pl.when(iq == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    q_lo, k_lo = iq * bq, ik * bk
    live = (k_lo <= q_lo + bq - 1) if causal else True
    if window and causal:
        # sliding window: q blocks entirely past the band see nothing here
        live = jnp.logical_and(live, k_lo + bk - 1 >= q_lo - window + 1)

    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0]                                  # (bq, 1)
        delta = delta_ref[0, 0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        mask = _block_mask_iota(q_lo, k_lo, bq, bk, causal, window)
        p = jnp.where(mask, jnp.exp(s - lse), 0.0)
        dv_scr[...] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        dk_scr[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if isinstance(live, bool):
        _compute()
    else:
        pl.when(live)(_compute)

    @pl.when(iq == nq - 1)
    def _finish():
        dk_ref[0, 0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[...].astype(dv_ref.dtype)


def flash_attention_bwd(q, k, v, o, lse, do, *, causal: bool = True,
                        window: int = 0, block_q: int | None = None,
                        block_k: int | None = None,
                        interpret: bool = False):
    """Returns (dq, dk, dv) with q/k/v in (B, S, H, D) layout and lse as
    the forward returns it, (B, Hq, Sq, 1). block_q/block_k None = auto
    (tuned cache)."""
    B, Sq, Hq, D = q.shape
    _, Sk, Hkv, _ = k.shape
    block_q, block_k = tuning.resolve_attention_blocks(
        block_q, block_k, q_shape=q.shape, k_shape=k.shape, dtype=q.dtype,
        causal=causal, window=window, kernel="flash_attention_bwd")
    g = Hq // Hkv
    bq, bk = min(block_q, Sq), min(block_k, Sk)
    nq, nk = Sq // bq, Sk // bk
    scale = 1.0 / np.sqrt(D)
    qt, kt, vt = (a.transpose(0, 2, 1, 3) for a in (q, k, v))
    dot_, ot = do.transpose(0, 2, 1, 3), o.transpose(0, 2, 1, 3)
    delta = jnp.sum(dot_.astype(jnp.float32) * ot.astype(jnp.float32),
                    axis=-1, keepdims=True)                    # (B,Hq,Sq,1)
    kw = dict(scale=scale, causal=causal, window=window, bq=bq, bk=bk)
    params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"))
    row_spec = pl.BlockSpec((1, 1, bq, 1), lambda b, h, i, j: (b, h, i, 0))
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, nk=nk, **kw),
        grid=(B, Hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, i, j: (b, h // g, j, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, i, j: (b, h // g, j, 0)),
            pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
            row_spec, row_spec,
        ],
        out_specs=pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hq, Sq, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
        compiler_params=params, interpret=interpret,
    )(qt, kt, vt, dot_, lse, delta)

    row_spec2 = pl.BlockSpec((1, 1, bq, 1), lambda b, h, j, i: (b, h, i, 0))
    dk_h, dv_h = pl.pallas_call(
        functools.partial(_dkv_kernel, nq=nq, **kw),
        grid=(B, Hq, nk, nq),
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, j, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, j, i: (b, h // g, j, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, j, i: (b, h // g, j, 0)),
            pl.BlockSpec((1, 1, bq, D), lambda b, h, j, i: (b, h, i, 0)),
            row_spec2, row_spec2,
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bk, D), lambda b, h, j, i: (b, h, j, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, j, i: (b, h, j, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct((B, Hq, Sk, D), jnp.float32),
                   jax.ShapeDtypeStruct((B, Hq, Sk, D), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((bk, D), jnp.float32),
                        pltpu.VMEM((bk, D), jnp.float32)],
        compiler_params=params, interpret=interpret,
    )(qt, kt, vt, dot_, lse, delta)
    # GQA: per-q-head dk/dv partials sum over the group
    dk = dk_h.reshape(B, Hkv, g, Sk, D).sum(2).transpose(0, 2, 1, 3)
    dv = dv_h.reshape(B, Hkv, g, Sk, D).sum(2).transpose(0, 2, 1, 3)
    return (dq.transpose(0, 2, 1, 3), dk.astype(k.dtype), dv.astype(v.dtype))
