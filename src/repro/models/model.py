"""Top-level model API: build(cfg, rt) -> Model with init/loss/prefill/decode.

Input contract per family (see DESIGN.md):
* dense/moe/ssm/hybrid : batch = {tokens:(B,S) i32, labels:(B,S) i32}
* vlm / early-fusion   : batch = {embeds:(B,S,d), positions:(B,3,S) i32,
                         labels:(B,S) i32}   (patch frontend stubbed)
* audio (whisper)      : batch = {audio_embeds:(B,S,d), tokens:(B,S) i32,
                         labels:(B,S) i32}   (conv/mel frontend stubbed)
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import transformer as tfm
from repro.models.layers import (
    embed_tokens,
    embedding_init,
    lm_logits,
    norm_init,
    apply_norm,
    sinusoidal_positions,
)
from repro.models.transformer import Runtime
from repro.runtime import trace_names as N


@dataclass(frozen=True)
class Model:
    cfg: ModelConfig
    rt: Runtime
    init_params: Callable
    loss: Callable          # (params, batch) -> (loss, aux)
    prefill: Callable       # (params, batch, cache_span) -> (logits, caches)
    decode_step: Callable   # (params, caches, token_batch, pos) -> (logits, caches)
    cache_init: Callable    # (batch,max_len,dtype) -> zeroed caches
    # paged-KV serving triple (full-attention decoder-only models; the
    # builders raise for families without a paged path):
    # (params, caches, tokens, block_tables, start_pos) -> (logits, caches)
    prefill_chunk: Callable = None
    # (params, caches, token, pos, block_tables) -> (logits, caches)
    decode_step_paged: Callable = None
    # (num_pages, page_size, dtype) -> zeroed paged pools
    paged_cache_init: Callable = None


def build(cfg: ModelConfig, rt: Runtime, param_dtype=jnp.bfloat16) -> Model:
    compute_dtype = param_dtype

    # ----------------------------------------------------------- params
    def init_params(key):
        k_emb, k_dec, k_enc = jax.random.split(key, 3)
        p = {
            "embed": embedding_init(k_emb, cfg, param_dtype),
            "layers": tfm.stack_init(k_dec, cfg, cfg.num_layers, param_dtype,
                                     cross=cfg.is_enc_dec),
            "final_norm": norm_init(cfg.d_model, cfg.norm),
        }
        if cfg.is_enc_dec:
            p["enc_layers"] = tfm.stack_init(
                k_enc, cfg, cfg.encoder_layers, param_dtype)
            p["enc_norm"] = norm_init(cfg.d_model, cfg.norm)
        return p

    # ----------------------------------------------------------- helpers
    def _embed_inputs(params, batch):
        """Returns (x, positions) for the decoder stack."""
        if cfg.frontend == "vision_stub" and "embeds" in batch:
            x = batch["embeds"].astype(compute_dtype)
            if cfg.rope == "mrope":
                positions = batch["positions"]
            else:
                positions = jnp.arange(x.shape[1])[None]
            return x, positions
        tokens = batch["tokens"]
        x = embed_tokens(params["embed"], tokens).astype(compute_dtype)
        S = x.shape[1]
        if cfg.rope == "sinusoidal":
            x = x + sinusoidal_positions(S, cfg.d_model).astype(x.dtype)
            positions = jnp.arange(S)[None]
        elif cfg.rope == "mrope":
            positions = jnp.broadcast_to(
                jnp.arange(S)[None, None], (x.shape[0], 3, S))
        else:
            positions = jnp.arange(S)[None]
        return x, positions

    def _encode(params, batch):
        x = batch["audio_embeds"].astype(compute_dtype)
        x = x + sinusoidal_positions(x.shape[1], cfg.d_model).astype(x.dtype)
        x, _ = tfm.stack_apply(params["enc_layers"], x, cfg, rt,
                               jnp.arange(x.shape[1])[None], causal=False)
        return apply_norm(params["enc_norm"], x, cfg.norm)

    # ----------------------------------------------------------- loss
    def loss(params, batch):
        enc_out = _encode(params, batch) if cfg.is_enc_dec else None
        with jax.named_scope(N.EMBED):
            x, positions = _embed_inputs(params, batch)
        x, aux = tfm.stack_apply(params["layers"], x, cfg, rt, positions,
                                 enc_out=enc_out, causal=True)
        x = apply_norm(params["final_norm"], x, cfg.norm)
        with jax.named_scope(N.LM_HEAD):
            logits = lm_logits(params["embed"], x, cfg.tie_embeddings,
                               true_vocab=cfg.vocab_size)
            logits = logits.astype(jnp.float32)
        with jax.named_scope(N.LOSS):
            labels = batch["labels"]
            logz = jax.scipy.special.logsumexp(logits, axis=-1)
            gold = jnp.take_along_axis(logits, labels[..., None],
                                       axis=-1)[..., 0]
            nll = (logz - gold).mean()
        total = nll + aux.get("aux_loss", 0.0)
        aux_out = {"nll": nll, **{k: v for k, v in aux.items()}}
        return total, aux_out

    # ----------------------------------------------------------- prefill
    def prefill(params, batch, cache_span: int):
        enc_out = _encode(params, batch) if cfg.is_enc_dec else None
        with jax.named_scope(N.EMBED):
            x, positions = _embed_inputs(params, batch)
        x, layer_caches = tfm.stack_prefill(params["layers"], x, cfg, rt,
                                            positions, enc_out=enc_out,
                                            cache_span=cache_span)
        caches = {"layers": layer_caches}
        if cfg.is_enc_dec:  # split cross-attention cache out of layer caches
            caches["cross"] = {"ck": layer_caches.pop("ck"),
                               "cv": layer_caches.pop("cv")}
        x_last = apply_norm(params["final_norm"], x[:, -1:], cfg.norm)
        return _logits(params, x_last), caches

    # ----------------------------------------------------------- decode
    def _sinusoidal_at(pos):
        """Closed-form sinusoidal position embedding at runtime ``pos``
        (any 1-D position vector) -> (len(pos), d_model) f32."""
        d = cfg.d_model
        half_idx = jnp.arange(0, d, 2)
        pos_v = jnp.atleast_1d(jnp.asarray(pos, jnp.float32))
        ang = pos_v[:, None] / jnp.power(10000.0, half_idx / d)
        pe = jnp.zeros((pos_v.shape[0], d), jnp.float32)
        return pe.at[:, 0::2].set(jnp.sin(ang)).at[:, 1::2].set(jnp.cos(ang))

    def _logits(params, x):
        """Float32 logits of the true vocabulary at ``x``."""
        with jax.named_scope(N.LM_HEAD):
            logits = lm_logits(params["embed"], x, cfg.tie_embeddings,
                               true_vocab=cfg.vocab_size)
            return logits.astype(jnp.float32)[..., :cfg.vocab_size]

    def _embed_decode(params, token, pos):
        with jax.named_scope(N.EMBED):
            x = embed_tokens(params["embed"], token).astype(compute_dtype)
            if cfg.rope == "sinusoidal":
                x = x + _sinusoidal_at(pos)[:, None].astype(x.dtype)
            return x

    def decode_step(params, caches, token, pos):
        """token: (B,1) i32; pos: scalar i32 (next position to write) or a
        (B,) vector of per-row positions (continuous batching)."""
        x = _embed_decode(params, token, pos)
        cross = caches.get("cross")
        x, new_layer_caches = tfm.stack_decode(
            params["layers"], x, caches["layers"], pos, cfg, rt,
            cross_caches=cross)
        x = apply_norm(params["final_norm"], x, cfg.norm)
        new_caches = dict(caches)
        new_caches["layers"] = new_layer_caches
        return _logits(params, x), new_caches

    # ------------------------------------------------------ paged serving
    def prefill_chunk(params, caches, tokens, block_tables, start_pos):
        """One chunk of a chunked prefill. tokens: (B, C) i32 at absolute
        positions ``start_pos .. start_pos+C-1``; caches: paged pools from
        ``paged_cache_init``; block_tables: (B, n_pages). Returns the
        logits of the chunk's LAST position ((B, 1, V)) and the updated
        pools — feeding the prompt chunk-by-chunk fills pages
        incrementally and the final chunk's logits seed decoding, exactly
        like one-shot ``prefill``."""
        C = tokens.shape[1]
        positions = start_pos + jnp.arange(C)
        with jax.named_scope(N.EMBED):
            x = embed_tokens(params["embed"], tokens).astype(compute_dtype)
            if cfg.rope == "sinusoidal":
                x = x + _sinusoidal_at(positions)[None].astype(x.dtype)
        x, new_layer = tfm.stack_prefill_chunk(
            params["layers"], x, caches["layers"], block_tables, positions,
            cfg, rt)
        x_last = apply_norm(params["final_norm"], x[:, -1:], cfg.norm)
        return _logits(params, x_last), {"layers": new_layer}

    def decode_step_paged(params, caches, token, pos, block_tables):
        """token: (B,1) i32; pos: (B,) next position per row;
        block_tables: (B, n_pages) physical page ids."""
        x = _embed_decode(params, token, pos)
        x, new_layer = tfm.stack_decode_paged(
            params["layers"], x, caches["layers"], pos, block_tables, cfg,
            rt)
        x = apply_norm(params["final_norm"], x, cfg.norm)
        return _logits(params, x), {"layers": new_layer}

    def paged_cache_init(num_pages: int, page_size: int,
                         dtype=param_dtype):
        return {"layers": tfm.paged_cache_init(cfg, cfg.num_layers,
                                               num_pages, page_size,
                                               dtype)}

    # ----------------------------------------------------------- caches
    def cache_init(batch: int, max_len: int, dtype=param_dtype,
                   enc_len: int = 0):
        caches = {"layers": tfm.cache_init(cfg, cfg.num_layers, batch,
                                           max_len, dtype)}
        if cfg.is_enc_dec:
            enc_len = enc_len or max_len
            hd, nkv = cfg.resolved_head_dim, cfg.num_kv_heads
            caches["cross"] = {
                "ck": jnp.zeros((cfg.num_layers, batch, enc_len, nkv, hd),
                                dtype),
                "cv": jnp.zeros((cfg.num_layers, batch, enc_len, nkv, hd),
                                dtype),
            }
        return caches

    return Model(cfg=cfg, rt=rt, init_params=init_params, loss=loss,
                 prefill=prefill, decode_step=decode_step,
                 cache_init=cache_init, prefill_chunk=prefill_chunk,
                 decode_step_paged=decode_step_paged,
                 paged_cache_init=paged_cache_init)
