"""Jitted public wrappers around the Pallas kernels.

Each call decides its mode from the platform it runs on
(:func:`interpret_mode`): on a TPU the kernels compile to Mosaic and
never interpret; on any other backend the kernel body runs in Pallas
interpret mode, the only way it can run there. Nothing is decided when
the module is imported, so importing it touches no device. The model
layer selects these via backend='pallas'.

Tile parameters default to ``None`` ("auto"): each wrapper resolves them
*eagerly* through the tuned-config cache (:mod:`repro.kernels.tuning`,
written by ``python -m benchmarks.run --tune``) before handing concrete
ints to jit as static args — so freshly tuned winners take effect in the
same process via a clean retrace, and a cache-less checkout keeps the
historical constants (128/128 blocks, chunk 64, 256 rows).
"""
from __future__ import annotations

from functools import partial

import numpy as np

import jax
import jax.numpy as jnp

from repro.kernels import tuning
from repro.kernels.flash_attention import (flash_attention_bwd,
                                           flash_attention_fwd)
from repro.kernels.paged_attention import paged_attention_fwd
from repro.kernels.rmsnorm import rmsnorm_fwd
from repro.kernels.rwkv6 import wkv6_fwd

def interpret_mode() -> bool:
    """Whether a kernel call made now runs in interpret mode: False on a
    TPU backend, True everywhere else."""
    return jax.default_backend() != "tpu"


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def _flash_attention(q, k, v, causal, window, block_q, block_k,
                     bwd_block_q, bwd_block_k, interpret):
    return flash_attention_fwd(q, k, v, causal=causal, window=window,
                               block_q=block_q, block_k=block_k,
                               interpret=interpret)


def _fa_fwd(q, k, v, causal, window, block_q, block_k, bwd_block_q,
            bwd_block_k, interpret):
    o, lse = flash_attention_fwd(q, k, v, causal=causal, window=window,
                                 block_q=block_q, block_k=block_k,
                                 interpret=interpret, return_lse=True)
    return o, (q, k, v, o, lse)


def _fa_bwd(causal, window, block_q, block_k, bwd_block_q, bwd_block_k,
            interpret, res, do):
    q, k, v, o, lse = res
    return flash_attention_bwd(q, k, v, o, lse, do, causal=causal,
                               window=window, block_q=bwd_block_q,
                               block_k=bwd_block_k, interpret=interpret)


_flash_attention.defvjp(_fa_fwd, _fa_bwd)

_flash_attention_jit = jax.jit(_flash_attention,
                               static_argnums=(3, 4, 5, 6, 7, 8, 9))


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    block_q: int | None = None,
                    block_k: int | None = None):
    """Differentiable flash attention: Pallas forward AND backward kernels
    (dq + dkv with saved logsumexp), custom_vjp-wired. block_q/block_k
    None = auto: forward and backward each resolve their own tuned tile
    config; an explicit value applies to both."""
    bq, bk = tuning.resolve_attention_blocks(
        block_q, block_k, q_shape=q.shape, k_shape=k.shape, dtype=q.dtype,
        causal=causal, window=window, kernel="flash_attention_fwd")
    bq_b, bk_b = tuning.resolve_attention_blocks(
        block_q, block_k, q_shape=q.shape, k_shape=k.shape, dtype=q.dtype,
        causal=causal, window=window, kernel="flash_attention_bwd")
    return _flash_attention_jit(q, k, v, causal, window, bq, bk, bq_b,
                                bk_b, interpret_mode())


@partial(jax.jit, static_argnames=("chunk", "interpret"))
def _wkv6_jit(q, k, v, ld, u=None, initial_state=None, *, chunk: int,
              interpret: bool):
    o, state = wkv6_fwd(q, k, v, ld, u, chunk=chunk, interpret=interpret)
    if initial_state is not None:
        # contribution of the carried-in state: q'_t @ (decay_t . S0)
        f32 = jnp.float32
        p_exc = jnp.cumsum(ld.astype(f32), axis=1) - (
            0.0 if u is None else ld.astype(f32))
        extra = jnp.einsum("bthk,bhkv->bthv",
                           q.astype(f32) * jnp.exp(p_exc),
                           initial_state.astype(f32))
        o = o + extra.astype(o.dtype)
        total_decay = jnp.exp(jnp.sum(ld.astype(f32), axis=1))  # (B,H,K)
        state = state + total_decay[..., None] * initial_state
    return o, state


def wkv6(q, k, v, ld, u=None, initial_state=None, *,
         chunk: int | None = None):
    """Matches models.ssm.linear_attention's (o, state) contract. A nonzero
    initial_state is folded in by running the state-only recurrence first.
    chunk None = auto (tuned cache -> 64)."""
    c = tuning.resolve_wkv_chunk(chunk, q_shape=q.shape,
                                 v_head=v.shape[-1], dtype=q.dtype,
                                 use_u=u is not None)
    return _wkv6_jit(q, k, v, ld, u, initial_state, chunk=c,
                     interpret=interpret_mode())


@partial(jax.jit, static_argnames=("pages_per_block", "interpret"))
def _paged_attention_jit(q, k_pages, v_pages, block_tables, lengths, layer,
                         *, pages_per_block: int, interpret: bool):
    return paged_attention_fwd(q, k_pages, v_pages, block_tables, lengths,
                               layer, pages_per_block=pages_per_block,
                               interpret=interpret)


def paged_decode_attention(q, k_pages, v_pages, block_tables, lengths,
                           layer=0, *, pages_per_block: int | None = None):
    """Block-table paged decode attention (no backward: decode only) over
    the stacked pools (L, P, page_size, Hkv, D) at ``layer``, or over one
    layer's pool (P, page_size, Hkv, D). pages_per_block None = auto
    (tuned cache -> 1)."""
    ppb = tuning.resolve_paged_pages_per_block(
        pages_per_block, q_shape=q.shape, pages_shape=k_pages.shape[-4:],
        n_pages=block_tables.shape[1], dtype=q.dtype)
    return _paged_attention_jit(q, k_pages, v_pages, block_tables, lengths,
                                layer, pages_per_block=ppb,
                                interpret=interpret_mode())


@partial(jax.jit, static_argnames=("eps", "block_rows", "interpret"))
def _rmsnorm_jit(x, scale, *, eps: float, block_rows: int, interpret: bool):
    return rmsnorm_fwd(x, scale, eps=eps, block_rows=block_rows,
                       interpret=interpret)


def rmsnorm(x, scale, *, eps: float = 1e-5, block_rows: int | None = None):
    """Fused RMSNorm. block_rows None = auto (tuned cache -> 256)."""
    br = tuning.resolve_rmsnorm_rows(
        block_rows, rows=int(np.prod(x.shape[:-1], dtype=np.int64)),
        d=x.shape[-1], dtype=x.dtype)
    return _rmsnorm_jit(x, scale, eps=eps, block_rows=br,
                        interpret=interpret_mode())
