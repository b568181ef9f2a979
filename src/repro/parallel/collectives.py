"""Collective building blocks implemented with shard_map.

partitioned_decode_attention: flash-decoding-style single-token attention
against a KV cache whose SEQUENCE dim is sharded over the `model` axis. Each
shard attends to its local cache slice and the partial (max, sum-exp,
weighted-value) triples are combined with two psums — the cache is never
gathered. This is what makes 32k-context decode of 100B-scale models fit
v5e HBM (gathering the cache would need ~85 GB/device).

compressed_psum_grads: int8 error-feedback gradient all-reduce over the data
axes (all-gather-of-quantized-shards form), used by the optional
``grad_compression='int8'`` run flag.
"""
from __future__ import annotations

from typing import Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P


def _shard_map(fn, in_specs, out_specs):
    return shard_map(fn, in_specs=in_specs, out_specs=out_specs,
                     check_vma=False)


def partitioned_decode_attention(q, k_cache, v_cache, cache_len,
                                 *, seq_axis: str = "model",
                                 batch_axes=("data",)):
    """q:(B,1,Hq,D); k_cache/v_cache:(B,S,Hkv,D) with S sharded over
    seq_axis and B over batch_axes; cache_len: scalar valid length, or a
    (B,) vector of per-row lengths (continuous batching)."""
    B, _, Hq, D = q.shape
    Hkv = k_cache.shape[2]
    g = Hq // Hkv
    bspec = batch_axes if batch_axes else None
    per_row = jnp.ndim(cache_len) > 0
    len_spec = P(bspec) if per_row else P()

    def local(q, k, v, cache_len):
        idx = jax.lax.axis_index(seq_axis)
        s_loc = k.shape[1]
        qg = q.reshape(-1, Hkv, g, D)
        s = jnp.einsum("bhgd,bkhd->bhgk", qg, k) / np.sqrt(D)
        s = s.astype(jnp.float32)
        gpos = idx * s_loc + jnp.arange(s_loc)
        mask = gpos[None, :] < jnp.reshape(cache_len, (-1, 1))
        s = jnp.where(mask[:, None, None, :], s, -1e30)
        m_loc = s.max(-1)                                     # (b,h,g)
        p = jnp.exp(s - m_loc[..., None])
        l_loc = p.sum(-1)
        o_loc = jnp.einsum("bhgk,bkhd->bhgd", p.astype(v.dtype), v)
        # lse-combine across sequence shards
        m_glob = jax.lax.pmax(m_loc, seq_axis)
        corr = jnp.exp(m_loc - m_glob)
        l_glob = jax.lax.psum(l_loc * corr, seq_axis)
        o = jax.lax.psum(o_loc * corr[..., None].astype(v.dtype), seq_axis)
        o = o / jnp.maximum(l_glob[..., None], 1e-30).astype(v.dtype)
        return o.reshape(-1, 1, Hq, D)

    return _shard_map(
        local,
        in_specs=(P(bspec, None, None, None), P(bspec, seq_axis, None, None),
                  P(bspec, seq_axis, None, None), len_spec),
        out_specs=P(bspec, None, None, None),
    )(q, k_cache, v_cache, cache_len)


# --------------------------------------------------------------------------
def int8_quantize(x) -> Tuple[jnp.ndarray, jnp.ndarray]:
    scale = jnp.maximum(jnp.abs(x).max(), 1e-8) / 127.0
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale


def compressed_psum_grads(grads, residuals, data_axes=("data",)):
    """Error-feedback int8 all-reduce over the data axes — the classic
    two-phase compressed ring: (1) quantize local chunks, all_to_all so
    each device owns one chunk's contributions; (2) sum exactly, re-quantize
    the owned chunk and all_gather. Both phases move int8 (~4x fewer wire
    bytes than an f32 ring all-reduce; 2x vs bf16), and the local
    quantization error is fed back into the next step's gradient.
    Returns (reduced_grads, new_residuals).
    """
    axis = data_axes if len(data_axes) > 1 else data_axes[0]

    def reduce_leaf(g, r):
        flat = g.reshape(-1).astype(jnp.float32) + r

        def body(x):
            n = jax.lax.psum(1, axis)
            pad = (-x.shape[0]) % n
            xp = jnp.pad(x, (0, pad))
            c = xp.shape[0] // n
            chunks = xp.reshape(n, c)
            # phase 1: per-chunk int8, all_to_all so device i owns chunk i
            scale = jnp.maximum(jnp.abs(chunks).max(axis=1), 1e-12) / 127.0
            q = jnp.clip(jnp.round(chunks / scale[:, None]),
                         -127, 127).astype(jnp.int8)
            qs = jax.lax.all_to_all(q, axis, split_axis=0, concat_axis=0)
            ss = jax.lax.all_to_all(scale[:, None], axis,
                                    split_axis=0, concat_axis=0)
            summed = (qs.astype(jnp.float32) * ss).sum(0)          # (c,)
            # phase 2: re-quantize the owned summed chunk, all_gather int8
            s2 = jnp.maximum(jnp.abs(summed).max(), 1e-12) / 127.0
            q2 = jnp.clip(jnp.round(summed / s2), -127, 127).astype(jnp.int8)
            out_q = jax.lax.all_gather(q2, axis, tiled=True)       # (n*c,)
            out_s = jax.lax.all_gather(s2[None], axis, tiled=True)  # (n,)
            out = (out_q.reshape(n, c).astype(jnp.float32)
                   * out_s[:, None]).reshape(-1)[: x.shape[0]]
            # error feedback: local phase-1 loss + (replicated) phase-2 loss
            err1 = (chunks - q.astype(jnp.float32)
                    * scale[:, None]).reshape(-1)[: x.shape[0]]
            err2 = jax.lax.all_gather(summed - q2.astype(jnp.float32) * s2,
                                      axis, tiled=True)[: x.shape[0]]
            return out, err1 + err2 / jnp.maximum(n, 1)
            # (err2/n: each device will re-contribute it next step)

        out, err = _shard_map(
            body, in_specs=P(None), out_specs=(P(None), P(None)))(flat)
        return out.reshape(g.shape).astype(g.dtype), err

    flat, treedef = jax.tree.flatten(grads)
    rflat, _ = jax.tree.flatten(residuals)
    outs = [reduce_leaf(g, r) for g, r in zip(flat, rflat)]
    new_grads = jax.tree.unflatten(treedef, [o[0] for o in outs])
    new_res = jax.tree.unflatten(treedef, [o[1] for o in outs])
    return new_grads, new_res
