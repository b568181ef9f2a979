"""Decoder stack composition: layer init/apply/decode for every assigned
family (dense, moe, vlm, audio enc-dec, hybrid attn+ssm, attention-free ssm),
scanned over a stacked-parameter leading layer axis so 80-layer models
compile as one HLO while-loop body.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec

from repro.configs.base import ModelConfig
from repro.models import attention as attn_mod
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.layers import (
    apply_mlp,
    apply_norm,
    apply_mrope,
    apply_rope,
    mlp_init,
    norm_init,
)
from repro.runtime.trace_names import ATTENTION, KV_WRITE, LAYERS, MLP, MOE


@dataclass(frozen=True)
class Runtime:
    """Execution knobs threaded through apply functions."""
    attention_backend: str = "dense"     # dense | chunked | pallas
    ssm_backend: str = "chunked"         # chunked | recurrent | pallas
    chunk: int = 512
    act_spec: Optional[PartitionSpec] = None   # (batch, seq, d_model)
    remat: bool = False
    # decode: lse-combining attention over a sequence-sharded KV cache
    decode_partitioned: bool = False
    mesh_batch_axes: tuple = ()          # axes the batch shards over
    dp_size: int = 1                     # product of mesh_batch_axes sizes
    moe_shardmap: bool = False           # expert-parallel shard_map dispatch
    ep_axes: tuple = ("model",)          # mesh axes experts shard over
    # §Perf: pin mixer/ffn outputs to the activation sharding BEFORE the
    # residual add, forcing the TP psum to run in bf16 instead of being
    # deferred into the f32 norm region (halves all-reduce bytes).
    pin_mixer_output: bool = False
    # §Perf: two-level factorized intra-chunk linear attention (no (c,c,K)
    # pairwise tensor) — see ssm.chunked_linear_attention.
    ssm_factored: bool = False
    # §Perf: remat in k-layer blocks (stack /k, recompute x k)
    layers_per_block: int = 1
    # §Perf: compute norms locally per device via shard_map. XLA otherwise
    # shards the f32 norm region over `model` and pays activation-sized f32
    # all-reduces to recombine cotangents in backward (measured: ~97% of
    # qwen1.5-110b's collective bytes).
    norm_local: bool = False
    # Pallas tile overrides for backend='pallas'. None = auto: resolved
    # per (kernel, shape, dtype, backend) from the tuned-config cache
    # (repro.kernels.tuning, written by `benchmarks.run --tune`), falling
    # back to the kernel defaults on a cache miss.
    attn_block_q: Optional[int] = None
    attn_block_k: Optional[int] = None
    ssm_chunk: Optional[int] = None
    # Paged decode attention (backend='pallas'): pages gathered per grid
    # step. None = auto (tuned cache, see repro.kernels.tuning).
    paged_pages_per_block: Optional[int] = None


def _constrain(x, rt: Runtime):
    if rt.act_spec is not None and x.ndim == 3:
        from repro.parallel.sharding import maybe_constrain
        return maybe_constrain(x, rt.act_spec)
    return x


def _norm(p_n, x, cfg: ModelConfig, rt: Runtime):
    """apply_norm, optionally forced device-local (rt.norm_local)."""
    from repro.parallel.sharding import have_ambient_mesh
    if not (rt.norm_local and rt.act_spec is not None
            and have_ambient_mesh() and x.ndim == 3):
        return apply_norm(p_n, x, cfg.norm)
    from jax.sharding import PartitionSpec as P
    pspecs = jax.tree.map(lambda _: P(None), p_n)
    return shard_map(
        lambda pn, xx: apply_norm(pn, xx, cfg.norm),
        in_specs=(pspecs, rt.act_spec), out_specs=rt.act_spec,
        check_vma=False)(p_n, x)


def _rope_q_k(cfg: ModelConfig, q, k, positions):
    if cfg.rope == "rope":
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    elif cfg.rope == "mrope":
        q = apply_mrope(q, positions, cfg.rope_theta)
        k = apply_mrope(k, positions, cfg.rope_theta)
    # 'sinusoidal' handled at embedding; 'none' is a no-op
    return q, k


def _attend(q, k, v, rt: Runtime, *, causal: bool, window: int = 0):
    """``attn_mod.attention`` under ``rt``'s backend. XLA cannot partition
    a Pallas kernel, so over a mesh of several devices the kernel runs
    per shard: batch over ``rt.mesh_batch_axes``, heads over ``model``
    when both head counts divide it (GQA groups stay whole, since head
    shards are contiguous)."""

    def run(q, k, v):
        return attn_mod.attention(q, k, v, backend=rt.attention_backend,
                                  causal=causal, window=window,
                                  chunk=rt.chunk, block_q=rt.attn_block_q,
                                  block_k=rt.attn_block_k)

    from repro.parallel.sharding import have_ambient_mesh
    if rt.attention_backend != "pallas" or not have_ambient_mesh():
        return run(q, k, v)
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.size == 1:
        return run(q, k, v)
    n_model = mesh.shape.get("model", 1)
    heads = ("model" if q.shape[2] % n_model == 0
             and k.shape[2] % n_model == 0 else None)
    spec = PartitionSpec(rt.mesh_batch_axes or None, None, heads, None)
    return shard_map(run, in_specs=(spec, spec, spec), out_specs=spec,
                     check_vma=False)(q, k, v)


def _ffn(p, h_in, cfg: ModelConfig, rt: Runtime):
    """The layer's MLP or MoE, each under its scope. Returns (h, aux)."""
    if cfg.moe is not None:
        with jax.named_scope(MOE):
            return moe_mod.moe_ffn(p["moe"], h_in, cfg, rt)
    with jax.named_scope(MLP):
        return apply_mlp(p["mlp"], h_in, cfg.activation), {}


# ===================================================================== init
def layer_init(key, cfg: ModelConfig, dtype, *, cross: bool = False,
               bidirectional: bool = False) -> dict:
    ks = jax.random.split(key, 8)
    p: dict = {"norm1": norm_init(cfg.d_model, cfg.norm),
               "norm2": norm_init(cfg.d_model, cfg.norm)}
    if cfg.family == "ssm":                       # rwkv6 block
        p["time_mix"] = ssm_mod.rwkv6_init(ks[0], cfg, dtype)
        p["channel_mix"] = ssm_mod.rwkv6_channel_mix_init(ks[1], cfg, dtype)
        return p
    p["attn"] = attn_mod.attn_init(ks[0], cfg, dtype)
    if cfg.family == "hybrid":
        p["ssm"] = ssm_mod.ssd_init(ks[1], cfg, dtype)
    if cross:
        p["norm_cross"] = norm_init(cfg.d_model, cfg.norm)
        p["cross_attn"] = attn_mod.attn_init(ks[2], cfg, dtype)
    if cfg.moe is not None:
        p["moe"] = moe_mod.moe_init(ks[3], cfg, dtype)
    else:
        p["mlp"] = mlp_init(ks[3], cfg.d_model, cfg.d_ff, cfg.activation,
                            dtype)
    return p


def stack_init(key, cfg: ModelConfig, num_layers: int, dtype, *,
               cross: bool = False, bidirectional: bool = False):
    keys = jax.random.split(key, num_layers)
    return jax.vmap(
        lambda k: layer_init(k, cfg, dtype, cross=cross,
                             bidirectional=bidirectional))(keys)


# ================================================================= forward
def _ring_from_prefill(k, span):
    """Arrange the last `span` prefill K/V rows into ring-buffer slot order
    (token at position p lives at slot p % span)."""
    B, S = k.shape[:2]
    take = min(S, span)
    k_last = k[:, S - take:]
    if take < span:
        k_last = jnp.pad(k_last, ((0, 0), (0, span - take)) +
                         ((0, 0),) * (k.ndim - 2))
    slots = (jnp.arange(span) + (S - take)) % span
    ring = jnp.zeros((B, span) + k.shape[2:], k.dtype)
    return ring.at[:, slots].set(k_last[:, :span])


def layer_apply(p, x, cfg: ModelConfig, rt: Runtime, positions,
                enc_out=None, *, causal: bool = True,
                return_cache: bool = False, cache_span: int = 0):
    """Full-sequence layer forward. Returns (x, aux_dict, cache_entry).

    cache_entry is None unless return_cache (prefill path), in which case it
    matches the per-layer structure of cache_init.
    """
    aux = {}
    if cfg.family == "ssm":
        h, (state, last_tok) = ssm_mod.rwkv6_time_mix(
            p["time_mix"], _norm(p["norm1"], x, cfg, rt), cfg,
            backend=rt.ssm_backend, factored=rt.ssm_factored,
            chunk=rt.ssm_chunk)
        x = _constrain(x + h, rt)
        h, last_tok2 = ssm_mod.rwkv6_channel_mix(
            p["channel_mix"], _norm(p["norm2"], x, cfg, rt))
        x = _constrain(x + h, rt)
        return x, aux, {"wkv_state": state, "shift1": last_tok,
                        "shift2": last_tok2}

    # ---- mixer: attention (+ parallel ssd heads for hybrid) ----
    h_in = _norm(p["norm1"], x, cfg, rt)
    window = cfg.window if cfg.attention_kind == "sliding" else 0
    with jax.named_scope(ATTENTION):
        q, k, v = attn_mod.project_qkv(p["attn"], h_in, h_in, cfg)
        q, k = _rope_q_k(cfg, q, k, positions)
        o = _attend(q, k, v, rt, causal=causal, window=window)
        h = o.reshape(*x.shape[:-1], -1) @ p["attn"]["wo"]
    cache_entry = {}
    if return_cache:
        if cfg.attention_kind == "sliding":
            span = min(cache_span, window) if window else cache_span
            cache_entry["k"] = _ring_from_prefill(k, span)
            cache_entry["v"] = _ring_from_prefill(v, span)
        else:
            pad = cache_span - k.shape[1]
            zpad = ((0, 0), (0, pad), (0, 0), (0, 0))
            cache_entry["k"] = jnp.pad(k, zpad)
            cache_entry["v"] = jnp.pad(v, zpad)
    if cfg.family == "hybrid":
        h_ssm, ssd_state = ssm_mod.ssd_mix(p["ssm"], h_in, cfg,
                                           backend=rt.ssm_backend,
                                           factored=rt.ssm_factored,
                                           chunk=rt.ssm_chunk)
        h = (h + h_ssm) * 0.5
        if return_cache:
            cache_entry["ssd_state"] = ssd_state
    if rt.pin_mixer_output:
        h = _constrain(h, rt)   # force the TP psum in bf16 (§Perf)
    x = _constrain(x + h, rt)

    # ---- cross attention (whisper decoder) ----
    if enc_out is not None:
        h_in = _norm(p["norm_cross"], x, cfg, rt)
        with jax.named_scope(ATTENTION):
            q, ck, cv = attn_mod.project_qkv(p["cross_attn"], h_in, enc_out,
                                             cfg)
            o = _attend(q, ck, cv, rt, causal=False)
            h = o.reshape(*x.shape[:-1], -1) @ p["cross_attn"]["wo"]
        x = _constrain(x + h, rt)
        if return_cache:
            cache_entry["ck"], cache_entry["cv"] = ck, cv

    # ---- mlp / moe ----
    h, ffn_aux = _ffn(p, _norm(p["norm2"], x, cfg, rt), cfg, rt)
    aux.update(ffn_aux)
    if rt.pin_mixer_output:
        h = _constrain(h, rt)   # force the TP psum in bf16 (§Perf)
    x = _constrain(x + h, rt)
    return x, aux, (cache_entry if return_cache else None)


def stack_apply(stacked, x, cfg: ModelConfig, rt: Runtime, positions,
                enc_out=None, *, causal: bool = True):
    """Scan the layer stack. Returns (x, aux) with aux reduced over layers.

    rt.layers_per_block > 1 (§Perf): remat in k-layer blocks — the saved
    activation stack shrinks k-fold (only block inputs are kept) at the
    price of recomputing k layers per backward block."""

    def one_layer(carry, p_layer):
        y, aux, _ = layer_apply(p_layer, carry, cfg, rt, positions, enc_out,
                                causal=causal)
        out_aux = {"aux_loss": aux.get("aux_loss", jnp.zeros(())),
                   "expert_load": aux.get("expert_load")}
        if out_aux["expert_load"] is None:
            out_aux.pop("expert_load")
        return y, out_aux

    k = max(1, rt.layers_per_block)
    L = jax.tree.leaves(stacked)[0].shape[0]
    if k > 1 and L % k == 0:
        blocked = jax.tree.map(
            lambda a: a.reshape(L // k, k, *a.shape[1:]), stacked)

        def body(carry, p_block):
            y, aux = jax.lax.scan(one_layer, carry, p_block)
            # aux_loss: (k,) -> scalar; expert_load: (k, E) kept, outer scan
            # stacks to (L/k, k, E) and we flatten to (L, E) at the end.
            return y, jax.tree.map(
                lambda a: a.sum(0) if a.ndim == 1 else a, aux)

        xs = blocked
    else:
        body, xs = one_layer, stacked

    fn = jax.checkpoint(body) if rt.remat else body
    with jax.named_scope(LAYERS):
        x, aux_stack = jax.lax.scan(fn, x, xs)
    aux = {"aux_loss": aux_stack["aux_loss"].sum()}
    if "expert_load" in aux_stack:
        el = aux_stack["expert_load"]
        aux["expert_load"] = el.reshape(-1, el.shape[-1])   # (L, E)
    return x, aux


def stack_prefill(stacked, x, cfg: ModelConfig, rt: Runtime, positions,
                  enc_out=None, *, cache_span: int):
    """Forward that also collects the stacked decode cache (prefill)."""

    def body(carry, p_layer):
        y, _, cache = layer_apply(p_layer, carry, cfg, rt, positions,
                                  enc_out, causal=True, return_cache=True,
                                  cache_span=cache_span)
        return y, cache

    with jax.named_scope(LAYERS):
        return jax.lax.scan(body, x, stacked)


# ================================================================= caches
def cache_init(cfg: ModelConfig, num_layers: int, batch: int, max_len: int,
               dtype) -> dict:
    """Stacked (L, ...) decode cache for one stack."""
    hd, nkv = cfg.resolved_head_dim, cfg.num_kv_heads
    L = num_layers
    c: dict = {}
    if cfg.attention_kind != "none":
        span = min(max_len, cfg.window) if cfg.attention_kind == "sliding" \
            else max_len
        c["k"] = jnp.zeros((L, batch, span, nkv, hd), dtype)
        c["v"] = jnp.zeros((L, batch, span, nkv, hd), dtype)
    if cfg.family == "hybrid":
        hs, N = cfg.ssm.head_size, cfg.ssm.state_size
        H = cfg.d_model // hs
        c["ssd_state"] = jnp.zeros((L, batch, H, N, hs), jnp.float32)
    if cfg.family == "ssm":
        hs = cfg.ssm.head_size
        H = cfg.d_model // hs
        c["wkv_state"] = jnp.zeros((L, batch, H, hs, hs), jnp.float32)
        c["shift1"] = jnp.zeros((L, batch, cfg.d_model), dtype)
        c["shift2"] = jnp.zeros((L, batch, cfg.d_model), dtype)
    return c


def paged_cache_init(cfg: ModelConfig, num_layers: int, num_pages: int,
                     page_size: int, dtype) -> dict:
    """Stacked (L, P, page_size, Hkv, D) paged KV pools. The pool is
    global — requests own *pages* via block tables, not slots — so there
    is no batch axis. Page 0 is the reserved null page (see
    :mod:`repro.serving.pages`)."""
    if (cfg.family in ("ssm", "hybrid") or cfg.attention_kind != "full"
            or cfg.is_enc_dec):
        raise ValueError(
            "paged KV serving supports full-attention decoder-only "
            f"models; got family={cfg.family!r}, "
            f"attention_kind={cfg.attention_kind!r}, "
            f"enc_dec={cfg.is_enc_dec}")
    hd, nkv = cfg.resolved_head_dim, cfg.num_kv_heads
    shape = (num_layers, num_pages, page_size, nkv, hd)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


# ================================================================== decode
def layer_decode(p, x, cache, pos, cfg: ModelConfig, rt: Runtime,
                 cross_cache=None):
    """Single-token step. x: (B,1,d); cache: this layer's entry (no L axis).
    Returns (x, new_cache).

    pos is a scalar (lockstep batch: every row writes the same cache slot)
    or a (B,) vector (continuous batching: each row carries its own
    position, writes its own slot, and masks its own valid cache length).
    """
    new_cache = dict(cache)
    pos = jnp.asarray(pos)
    per_row = pos.ndim > 0
    if cfg.family == "ssm":
        h_in = apply_norm(p["norm1"], x, cfg.norm)
        h, (state, _) = ssm_mod.rwkv6_time_mix(
            p["time_mix"], h_in, cfg, backend="recurrent",
            state=cache["wkv_state"], shift_prev=cache["shift1"])
        x = x + h
        new_cache["wkv_state"] = state
        new_cache["shift1"] = h_in[:, 0]
        h_in = apply_norm(p["norm2"], x, cfg.norm)
        h, _ = ssm_mod.rwkv6_channel_mix(p["channel_mix"], h_in,
                                         shift_prev=cache["shift2"])
        new_cache["shift2"] = h_in[:, 0]
        return x + h, new_cache

    h_in = apply_norm(p["norm1"], x, cfg.norm)
    with jax.named_scope(ATTENTION):
        q, k, v = attn_mod.project_qkv(p["attn"], h_in, h_in, cfg)
        pos_b = jnp.broadcast_to(pos.reshape(-1, 1), (x.shape[0], 1))
        q, k = _rope_q_k(cfg, q, k, pos_b if cfg.rope != "mrope" else
                         jnp.broadcast_to(pos_b[:, None],
                                          (x.shape[0], 3, 1)))
    span = cache["k"].shape[1]
    slot = pos % span if cfg.attention_kind == "sliding" else pos
    with jax.named_scope(KV_WRITE):
        if per_row:
            bidx = jnp.arange(x.shape[0])
            k_cache = cache["k"].at[bidx, slot].set(k[:, 0])
            v_cache = cache["v"].at[bidx, slot].set(v[:, 0])
        else:
            k_cache = jax.lax.dynamic_update_slice_in_dim(cache["k"], k,
                                                          slot, axis=1)
            v_cache = jax.lax.dynamic_update_slice_in_dim(cache["v"], v,
                                                          slot, axis=1)
    cache_len = jnp.minimum(pos + 1, span)
    with jax.named_scope(ATTENTION):
        if rt.decode_partitioned and cfg.attention_kind == "full":
            from repro.parallel.collectives import \
                partitioned_decode_attention
            o = partitioned_decode_attention(q, k_cache, v_cache, cache_len,
                                             batch_axes=rt.mesh_batch_axes)
        else:
            o = attn_mod.decode_attention_simple(q, k_cache, v_cache,
                                                 cache_len)
        h = o.reshape(*x.shape[:-1], -1) @ p["attn"]["wo"]
    new_cache["k"], new_cache["v"] = k_cache, v_cache
    if cfg.family == "hybrid":
        # one-step ssd
        sp = p["ssm"]
        B, _, dm = x.shape
        hs, N = cfg.ssm.head_size, cfg.ssm.state_size
        H = sp["wx"].shape[1] // hs
        xin = (h_in @ sp["wx"]).reshape(B, H, hs)
        z = jax.nn.silu(h_in @ sp["wz"])[:, 0]
        Bm = (h_in @ sp["wB"]).reshape(B, H, N)
        Cm = (h_in @ sp["wC"]).reshape(B, H, N)
        dt = jax.nn.softplus((h_in @ sp["wdt"]).astype(jnp.float32)[:, 0]
                             + sp["dt_bias"])
        ld = jnp.broadcast_to(
            jnp.clip((-dt * jnp.exp(sp["A_log"]))[..., None], -12.0, -1e-6),
            (B, H, N))
        state, o_s = ssm_mod.linear_attention_step(
            cache["ssd_state"], Cm.astype(jnp.float32),
            (Bm * dt[..., None].astype(Bm.dtype)).astype(jnp.float32),
            xin.astype(jnp.float32), ld)
        o_s = o_s + sp["D"][:, None] * xin.astype(jnp.float32)
        h_ssm = (o_s.reshape(B, H * hs).astype(x.dtype) * z) @ sp["wo"]
        h = (h + h_ssm[:, None]) * 0.5
        new_cache["ssd_state"] = state
    x = x + h

    if cross_cache is not None:
        h_in = apply_norm(p["norm_cross"], x, cfg.norm)
        hd = cfg.resolved_head_dim
        q = (h_in @ p["cross_attn"]["wq"])
        if cfg.qkv_bias:
            q = q + p["cross_attn"]["bq"]
        q = q.reshape(x.shape[0], 1, cfg.num_heads, hd)
        enc_len = jnp.int32(cross_cache["ck"].shape[1])
        if rt.decode_partitioned:
            from repro.parallel.collectives import \
                partitioned_decode_attention
            o = partitioned_decode_attention(
                q, cross_cache["ck"], cross_cache["cv"], enc_len,
                batch_axes=rt.mesh_batch_axes)
        else:
            o = attn_mod.decode_attention_simple(
                q, cross_cache["ck"], cross_cache["cv"], enc_len)
        x = x + o.reshape(*x.shape[:-1], -1) @ p["cross_attn"]["wo"]

    h, _ = _ffn(p, apply_norm(p["norm2"], x, cfg.norm), cfg, rt)
    return x + h, new_cache


def _paged_attend(q, k_pools, v_pools, block_tables, lengths, layer,
                  rt: Runtime):
    """Backend switch for block-table attention over the stacked pools at
    ``layer``: the Pallas kernel (with its in-kernel page gather) for
    backend='pallas', the gather-then-decode_attention_simple reference
    everywhere else."""
    if rt.attention_backend == "pallas":
        from repro.kernels import ops as kops
        return kops.paged_decode_attention(
            q, k_pools, v_pools, block_tables, lengths, layer,
            pages_per_block=rt.paged_pages_per_block)
    return attn_mod.paged_decode_attention_ref(q, k_pools, v_pools,
                                               block_tables, lengths, layer)


def layer_decode_paged(p, x, pools, layer, pos, block_tables,
                       cfg: ModelConfig, rt: Runtime):
    """Single-token step against the paged KV pool. x: (B,1,d); pools:
    the stacked {"k","v"} pools (L, P, page_size, Hkv, D) — no batch
    axis — of which this is layer ``layer``; pos: (B,) per-row positions;
    block_tables: (B, n_pages) physical page ids in logical order. A
    retired row's table is all null, and the Pallas kernel relies on
    that: it walks no page of a row whose first entry is the null page,
    whatever stale position the row keeps. Each row writes its new K/V
    at (layer, table[pos // page_size], pos % page_size) — rows own
    disjoint pages, so the scatter never races — and the kernel reads
    the same stacked pool at ``layer``: no layer's pool is sliced out or
    written back."""
    pos = jnp.asarray(pos)
    h_in = apply_norm(p["norm1"], x, cfg.norm)
    with jax.named_scope(ATTENTION):
        q, k, v = attn_mod.project_qkv(p["attn"], h_in, h_in, cfg)
        pos_b = jnp.broadcast_to(pos.reshape(-1, 1), (x.shape[0], 1))
        q, k = _rope_q_k(cfg, q, k, pos_b if cfg.rope != "mrope" else
                         jnp.broadcast_to(pos_b[:, None],
                                          (x.shape[0], 3, 1)))
    with jax.named_scope(KV_WRITE):
        ps = pools["k"].shape[2]
        bidx = jnp.arange(x.shape[0])
        pages = block_tables[bidx, pos // ps]
        offs = pos % ps
        k_pools = pools["k"].at[layer, pages, offs].set(k[:, 0])
        v_pools = pools["v"].at[layer, pages, offs].set(v[:, 0])
    with jax.named_scope(ATTENTION):
        o = _paged_attend(q, k_pools, v_pools, block_tables, pos + 1, layer,
                          rt)
        h = o.reshape(*x.shape[:-1], -1) @ p["attn"]["wo"]
    x = x + h
    h, _ = _ffn(p, apply_norm(p["norm2"], x, cfg.norm), cfg, rt)
    return x + h, {"k": k_pools, "v": v_pools}


def _scan_paged(layer_fn, stacked, x, pools):
    """Scan ``layer_fn(p_layer, layer, x, pools) -> (x, pools)`` over the
    layer stack with the stacked pools in the carry, so that each layer
    updates them in place at its own index: pools passed as scan ``xs``
    and ``ys`` would be sliced out and stacked back, a copy of the whole
    pool every step."""
    L = jax.tree.leaves(stacked)[0].shape[0]

    def body(carry, xs):
        return layer_fn(*xs, *carry), None

    with jax.named_scope(LAYERS):
        (x, pools), _ = jax.lax.scan(body, (x, pools),
                                     (stacked, jnp.arange(L)))
    return x, pools


def stack_decode_paged(stacked, x, pools, pos, block_tables,
                       cfg: ModelConfig, rt: Runtime):
    """Paged decode through the layer stack; block tables are shared
    across layers (one logical address space, L physical pools)."""
    return _scan_paged(
        lambda p, layer, y, pools: layer_decode_paged(
            p, y, pools, layer, pos, block_tables, cfg, rt),
        stacked, x, pools)


def layer_prefill_chunk(p, x, pools, layer, block_tables, positions,
                        cfg: ModelConfig, rt: Runtime):
    """Chunked-prefill layer step: write this chunk's K/V into layer
    ``layer`` of the stacked paged pools (L, P, page_size, Hkv, D), then
    attend causally over the *gathered* logical history (pages written by
    earlier chunks plus this one). x: (B, C, d); positions: (C,) absolute
    token positions of the chunk.

    The chunk is small and prefill is compute-bound, so the gather runs
    outside any kernel and the scores go through ``dense_attention`` with
    ``q_offset`` — the same masked-softmax math as the one-shot prefill,
    summed in the same (logical-position) order. The gather reads the
    table's pages of this layer only, never a layer-sized slice."""
    h_in = apply_norm(p["norm1"], x, cfg.norm)
    with jax.named_scope(ATTENTION):
        q, k, v = attn_mod.project_qkv(p["attn"], h_in, h_in, cfg)
        q, k = _rope_q_k(cfg, q, k, positions[None] if cfg.rope != "mrope"
                         else jnp.broadcast_to(positions[None, None],
                                               (1, 3, positions.shape[0])))
    B, C = x.shape[0], x.shape[1]
    ps = pools["k"].shape[2]
    npag = block_tables.shape[1]
    with jax.named_scope(KV_WRITE):
        pages = jnp.take(block_tables, positions // ps, axis=1)  # (B, C)
        offs = jnp.broadcast_to((positions % ps)[None], (B, C))
        k_pools = pools["k"].at[layer, pages, offs].set(k)
        v_pools = pools["v"].at[layer, pages, offs].set(v)
    with jax.named_scope(ATTENTION):
        k_all = k_pools[layer, block_tables].reshape(B, npag * ps,
                                                     *k.shape[2:])
        v_all = v_pools[layer, block_tables].reshape(B, npag * ps,
                                                     *v.shape[2:])
        o = attn_mod.dense_attention(q, k_all, v_all, causal=True,
                                     q_offset=positions[0])
        h = o.reshape(*x.shape[:-1], -1) @ p["attn"]["wo"]
    x = x + h
    h, _ = _ffn(p, apply_norm(p["norm2"], x, cfg.norm), cfg, rt)
    return x + h, {"k": k_pools, "v": v_pools}


def stack_prefill_chunk(stacked, x, pools, block_tables, positions,
                        cfg: ModelConfig, rt: Runtime):
    """One prompt chunk through the layer stack, the stacked paged pools
    in the scan's carry."""
    return _scan_paged(
        lambda p, layer, y, pools: layer_prefill_chunk(
            p, y, pools, layer, block_tables, positions, cfg, rt),
        stacked, x, pools)


def stack_decode(stacked, x, caches, pos, cfg: ModelConfig, rt: Runtime,
                 cross_caches=None):
    """Scan decode over layers, threading per-layer caches as scan xs/ys."""

    def body(carry, xs):
        if cross_caches is not None:
            p_layer, cache, ccache = xs
        else:
            p_layer, cache = xs
            ccache = None
        y, new_cache = layer_decode(p_layer, carry, cache, pos, cfg, rt,
                                    cross_cache=ccache)
        return y, new_cache

    xs = (stacked, caches, cross_caches) if cross_caches is not None \
        else (stacked, caches)
    with jax.named_scope(LAYERS):
        return jax.lax.scan(body, x, xs)
