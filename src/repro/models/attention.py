"""Attention: GQA projections + three interchangeable score backends.

Backends
--------
* ``dense``   — materializes the (S, S) score matrix. Smoke tests only.
* ``chunked`` — blockwise online-softmax attention in pure jnp. Outer python
  loop over query chunks (static), inner ``lax.scan`` over kv chunks, so only
  the causal lower triangle of blocks is ever computed and peak memory is
  O(chunk^2) — this is the CPU-lowerable stand-in for the Pallas kernel and
  the backend the multi-pod dry-run compiles.
* ``pallas``  — the TPU flash-attention kernel from ``repro.kernels``.

All backends take q:(B,S,Hq,D), k/v:(B,Sk,Hkv,D) with Hq a multiple of Hkv
(grouped-query attention) and never materialize repeated KV heads.
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig


def attn_init(key, cfg: ModelConfig, dtype, cross: bool = False) -> dict:
    d, hd = cfg.d_model, cfg.resolved_head_dim
    nq, nkv = cfg.num_heads, cfg.num_kv_heads
    ks = jax.random.split(key, 4)
    s = 1.0 / np.sqrt(d)
    p = {
        "wq": (jax.random.normal(ks[0], (d, nq * hd)) * s).astype(dtype),
        "wk": (jax.random.normal(ks[1], (d, nkv * hd)) * s).astype(dtype),
        "wv": (jax.random.normal(ks[2], (d, nkv * hd)) * s).astype(dtype),
        "wo": (jax.random.normal(ks[3], (nq * hd, d)) / np.sqrt(nq * hd)).astype(dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((nq * hd,), dtype)
        p["bk"] = jnp.zeros((nkv * hd,), dtype)
        p["bv"] = jnp.zeros((nkv * hd,), dtype)
    return p


def project_qkv(p, xq, xkv, cfg: ModelConfig):
    """Returns q:(B,S,Hq,D), k,v:(B,Sk,Hkv,D)."""
    hd = cfg.resolved_head_dim
    q = xq @ p["wq"]
    k = xkv @ p["wk"]
    v = xkv @ p["wv"]
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(*q.shape[:-1], cfg.num_heads, hd)
    k = k.reshape(*k.shape[:-1], cfg.num_kv_heads, hd)
    v = v.reshape(*v.shape[:-1], cfg.num_kv_heads, hd)
    return q, k, v


# ------------------------------------------------------------------ dense
def dense_attention(q, k, v, *, causal: bool, window: int = 0,
                    q_offset: int = 0) -> jnp.ndarray:
    """Reference/smoke backend. Handles GQA by reshaping q into groups."""
    B, Sq, Hq, D = q.shape
    _, Sk, Hkv, _ = k.shape
    g = Hq // Hkv
    qg = q.reshape(B, Sq, Hkv, g, D)
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k) / np.sqrt(D)
    scores = scores.astype(jnp.float32)
    qpos = jnp.arange(Sq) + q_offset
    kpos = jnp.arange(Sk)
    mask = jnp.ones((Sq, Sk), bool)
    if causal:
        mask &= qpos[:, None] >= kpos[None, :]
    if window:
        mask &= qpos[:, None] - kpos[None, :] < window
    scores = jnp.where(mask, scores, -1e30)
    w = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", w, v)
    return out.reshape(B, Sq, Hq, D)


# ------------------------------------------------------------------ chunked
def _block_mask(qpos, kpos, causal, window):
    m = jnp.ones((qpos.shape[0], kpos.shape[0]), bool)
    if causal:
        m &= qpos[:, None] >= kpos[None, :]
    if window:
        m &= qpos[:, None] - kpos[None, :] < window
    return m


def chunked_attention(q, k, v, *, causal: bool, window: int = 0,
                      chunk: int = 1024) -> jnp.ndarray:
    """Blockwise flash-style attention; computes only blocks that can
    contain unmasked entries."""
    B, S, Hq, D = q.shape
    _, Sk, Hkv, _ = k.shape
    g = Hq // Hkv
    c = min(chunk, S, Sk)
    # pad to multiple of c
    pad_q = (-S) % c
    pad_k = (-Sk) % c
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    nq, nk = q.shape[1] // c, k.shape[1] // c
    qg = q.reshape(B, nq, c, Hkv, g, D)
    kc = k.reshape(B, nk, c, Hkv, D)
    vc = v.reshape(B, nk, c, Hkv, D)
    scale = 1.0 / np.sqrt(D)

    outs = []
    for i in range(nq):  # static outer loop -> only needed blocks compiled
        qi = qg[:, i] * scale                       # (B,c,Hkv,g,D)
        jlo = 0
        jhi = min(i + 1, nk) if causal else nk
        if window:
            jlo = max(0, (i * c - window + 1) // c)  # chunk of earliest visible kpos
        qpos = jnp.arange(c) + i * c

        def kv_step(carry, xs):
            acc, m_run, l_run = carry
            kj, vj, j = xs
            kpos = j * c + jnp.arange(c)
            s = jnp.einsum("bchgd,bkhd->bhgck", qi, kj).astype(jnp.float32)
            mask = _block_mask(qpos, kpos, causal, window)
            s = jnp.where(mask[None, None, None], s, -1e30)
            m_new = jnp.maximum(m_run, s.max(-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m_run - m_new)
            l_new = l_run * corr + p.sum(-1)
            acc = acc * corr[..., None] + jnp.einsum(
                "bhgck,bkhd->bhgcd", p.astype(qi.dtype), vj).astype(jnp.float32)
            return (acc, m_new, l_new), None

        acc0 = jnp.zeros((B, Hkv, g, c, D), jnp.float32)
        m0 = jnp.full((B, Hkv, g, c), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((B, Hkv, g, c), jnp.float32)
        js = jnp.arange(jlo, jhi)
        (acc, m_run, l_run), _ = jax.lax.scan(
            kv_step, (acc0, m0, l0),
            (kc[:, jlo:jhi].swapaxes(0, 1), vc[:, jlo:jhi].swapaxes(0, 1), js))
        oi = acc / jnp.maximum(l_run[..., None], 1e-30)
        outs.append(oi.transpose(0, 3, 1, 2, 4).reshape(B, c, Hq, D))
    out = jnp.concatenate(outs, axis=1)[:, :S]
    return out.astype(q.dtype)


# ------------------------------------------------------------------ decode
def decode_attention_simple(q, k_cache, v_cache, cache_len) -> jnp.ndarray:
    """One-token decode against a full cache. q:(B,1,Hq,D),
    caches:(B,Smax,Hkv,D); positions >= cache_len are masked. cache_len is
    a scalar (lockstep batch) or a (B,) vector (continuous batching: each
    slot carries its own valid length)."""
    B, _, Hq, D = q.shape
    _, Sk, Hkv, _ = k_cache.shape
    g = Hq // Hkv
    qg = q.reshape(B, Hkv, g, D)
    s = jnp.einsum("bhgd,bkhd->bhgk", qg, k_cache) / np.sqrt(D)
    s = s.astype(jnp.float32)
    valid = jnp.arange(Sk)[None, :] < jnp.reshape(cache_len, (-1, 1))
    s = jnp.where(valid[:, None, None, :], s, -1e30)
    w = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    o = jnp.einsum("bhgk,bkhd->bhgd", w, v_cache)
    return o.reshape(B, 1, Hq, D)


def paged_decode_attention_ref(q, k_pages, v_pages, block_tables,
                               lengths, layer=0) -> jnp.ndarray:
    """Reference paged decode attention: gather the per-row pages into a
    contiguous logical cache and reuse :func:`decode_attention_simple`.
    q:(B,1,Hq,D); k_pages/v_pages: the stacked pools (L,P,ps,Hkv,D), read
    at ``layer``, or one layer's pool (P,ps,Hkv,D) with ``layer`` 0;
    block_tables:(B,npag) physical page ids in logical order; lengths:(B,)
    valid KV tokens.

    Gathered logical order == position order, so the masked positions and
    the softmax summation order match both the monolithic decode path and
    the Pallas kernel (which gathers inside the kernel instead)."""
    if k_pages.ndim == 4:
        k_pages, v_pages = k_pages[None], v_pages[None]
    B = q.shape[0]
    ps, Hkv, D = k_pages.shape[2:]
    npag = block_tables.shape[1]
    k = k_pages[layer, block_tables].reshape(B, npag * ps, Hkv, D)
    v = v_pages[layer, block_tables].reshape(B, npag * ps, Hkv, D)
    return decode_attention_simple(q, k, v, lengths)


def attention(q, k, v, *, backend: str, causal: bool, window: int = 0,
              chunk: int = 1024, block_q: int = None,
              block_k: int = None) -> jnp.ndarray:
    """block_q/block_k only apply to the pallas backend; None = auto
    (resolved from the tuned-config cache, see repro.kernels.tuning)."""
    if backend == "dense":
        return dense_attention(q, k, v, causal=causal, window=window)
    if backend == "chunked":
        return chunked_attention(q, k, v, causal=causal, window=window,
                                 chunk=chunk)
    if backend == "pallas":
        from repro.kernels import ops as kops
        return kops.flash_attention(q, k, v, causal=causal, window=window,
                                    block_q=block_q, block_k=block_k)
    raise ValueError(f"unknown attention backend {backend!r}")
