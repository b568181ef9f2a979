"""Chunked WKV6 (data-dependent-decay linear attention) — Pallas TPU kernel.

Grid: (B, H, T/chunk) with the chunk dim SEQUENTIAL so the (K, V) recurrent
state lives in VMEM scratch across chunks. Per chunk the kernel computes

  o_t = q'_t @ S  +  sum_{s<t} (q_t . k_s . exp(p_{t-1}-p_s)) v_s  [+ u bonus]
  S  <- exp(p_last) . S  +  sum_s (k_s exp(p_last - p_s)) (x) v_s

with all decay factors exp(<=0) (numerically safe; see models/ssm.py for
the derivation).

The intra-chunk attention A[t,s] = q_t . (k_s exp(w_t - p_s)) is computed
as a decay-rescaled matmul (q exp(w)) @ (k exp(-p)).T so the inner loop is
MXU work; exp(-p) grows with the in-chunk decay range, so when that range
exceeds SAFE_DECAY_RANGE the kernel falls back to the masked (c, c, K)
pairwise-decay tensor (c=64, K=64 -> 1 MB f32, well inside the 16 MB
budget). Chunk matmuls are MXU-aligned at (64, 64).

Supports both rwkv6 mode (bonus u, current token excluded from the state
it sees) and SSD mode (u=None, current token included). ``chunk=None``
("auto") resolves through the tuned-config cache
(:mod:`repro.kernels.tuning`, populated by ``benchmarks.run --tune``),
falling back to the historical chunk=64.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import tuning

# Largest in-chunk |cumsum(ld)| for which the decay-rescaled matmul path
# is used: factors stay <= exp(30) ~ 1e13, far from f32 overflow even
# after the (masked-out) upper-triangle products and the K-dim reduction.
SAFE_DECAY_RANGE = 30.0


def _wkv_kernel(q_ref, k_ref, v_ref, ld_ref, u_ref, o_ref, state_out_ref,
                s_scr, *, chunk: int, n_chunks: int, use_u: bool):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        s_scr[...] = jnp.zeros_like(s_scr)

    c = chunk
    q = q_ref[0, 0].astype(jnp.float32)          # (c, K)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)          # (c, V)
    ld = ld_ref[0, 0].astype(jnp.float32)        # (c, K)

    t_i = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    s_i = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    # Mosaic has no cumsum: the in-chunk prefix sum is a lower-triangular
    # ones matmul, at full f32 precision so the decays stay exact
    p_inc = jax.lax.dot_general(
        (t_i >= s_i).astype(jnp.float32), ld, (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)
    p_exc = p_inc - ld
    w_exp = p_exc if use_u else p_inc

    # intra-chunk attention A[t,s] = q_t . (k_s exp(w_t - p_s)), s <(=) t
    mask = (t_i > s_i) if use_u else (t_i >= s_i)

    def _intra_matmul(_):
        # decay-rescaled matmul (MXU path): exp(w) <= 1 and exp(-p) is
        # bounded by exp(SAFE_DECAY_RANGE), so both factors are finite
        qs = q * jnp.exp(w_exp)
        ks = k * jnp.exp(-p_inc)
        a = jax.lax.dot_general(qs, ks, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        return jnp.where(mask, a, 0.0)

    def _intra_pairwise(_):
        # masked fallback: exact per-pair decay, (c, c, K) tensor in VMEM.
        # Unmasked pairs have w_t <= p_s, so clamping at 0 changes none of
        # them and keeps the masked ones finite; the mask is applied on
        # (c, c) and the product reduced by hand (Mosaic has neither a
        # batched dot_general nor a reshape of a boolean vector)
        diff = jnp.minimum(w_exp[:, None, :] - p_inc[None, :, :], 0.0)
        a = jnp.sum(q[:, None, :] * k[None, :, :] * jnp.exp(diff), axis=-1)
        return jnp.where(mask, a, 0.0)

    # p_inc is a cumsum of ld <= 0, so -min(p_inc) is the chunk's largest
    # decay magnitude; beyond SAFE_DECAY_RANGE exp(-p_inc) would overflow
    a = jax.lax.cond(-jnp.min(p_inc) < SAFE_DECAY_RANGE,
                     _intra_matmul, _intra_pairwise, 0)
    o = jax.lax.dot_general(a.astype(v.dtype), v,
                            (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    if use_u:
        u = u_ref[0].astype(jnp.float32)                      # (1, K)
        diag = jnp.sum(q * u * k, axis=1, keepdims=True)
        o = o + diag * v

    # cross-chunk state contribution + recurrence
    S = s_scr[...]                                            # (K, V)
    o = o + jax.lax.dot_general((q * jnp.exp(w_exp)), S,
                                (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
    p_last = p_inc[-1:, :]                                    # (1, K)
    k_dec = k * jnp.exp(p_last - p_inc)
    s_scr[...] = jnp.exp(p_last).T * S + jax.lax.dot_general(
        k_dec, v, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    o_ref[0, 0] = o.astype(o_ref.dtype)

    @pl.when(ic == n_chunks - 1)
    def _emit_state():
        state_out_ref[0, 0] = s_scr[...]


def wkv6_fwd(q, k, v, ld, u=None, *, chunk: int | None = None,
             interpret: bool = False):
    """q/k/ld: (B, T, H, K); v: (B, T, H, V); u: (H, K) or None.
    Returns (o (B,T,H,V), state (B,H,K,V)). chunk None = auto (tuned)."""
    B, T, H, K = q.shape
    V = v.shape[-1]
    chunk = tuning.resolve_wkv_chunk(chunk, q_shape=q.shape, v_head=V,
                                     dtype=q.dtype, use_u=u is not None)
    c = min(chunk, T)
    if T % c:
        raise ValueError(f"wkv6 chunk must tile the sequence: T={T} c={c}")
    n = T // c
    use_u = u is not None
    if u is None:
        u = jnp.zeros((H, K), jnp.float32)
    # (H, 1, K): the unit dim keeps the (1, 1, K) block within Mosaic's
    # rule for the last two block dims
    u = u.reshape(H, 1, K)

    def tr(x):
        return x.transpose(0, 2, 1, 3)    # (B, H, T, *)

    kernel = functools.partial(_wkv_kernel, chunk=c, n_chunks=n, use_u=use_u)
    o, state = pl.pallas_call(
        kernel,
        grid=(B, H, n),
        in_specs=[
            pl.BlockSpec((1, 1, c, K), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, c, K), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, c, V), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, c, K), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, K), lambda b, h, i: (h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, c, V), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, K, V), lambda b, h, i: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, T, V), q.dtype),
            jax.ShapeDtypeStruct((B, H, K, V), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((K, V), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(tr(q), tr(k), tr(v), tr(ld), u)
    return o.transpose(0, 2, 1, 3), state
