"""Mixture-of-Experts FFN with sort-based (dropping) token dispatch.

Dispatch is gather/scatter-based, NOT the one-hot-einsum GShard form: the
einsum dispatch costs 2*T*d*E*C flops which, at 128 experts, exceeds the
expert FFN compute by >50x and would poison the roofline. Sorting tokens by
expert id and gathering into capacity buffers keeps dispatch compute
negligible, matching how MegaBlocks-style systems behave.

Expert weights are stacked (E, d, f) so the expert dimension can shard over
the ``model`` mesh axis (expert parallelism).
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.lax import axis_size

from repro.configs.base import ModelConfig, MoEConfig


def moe_init(key, cfg: ModelConfig, dtype) -> dict:
    e = cfg.moe
    d, f = cfg.d_model, e.expert_ff
    ks = jax.random.split(key, 5)
    s_in, s_out = 1.0 / np.sqrt(d), 1.0 / np.sqrt(f)
    p = {
        "router": (jax.random.normal(ks[0], (d, e.num_experts)) * s_in).astype(jnp.float32),
        "w_in": (jax.random.normal(ks[1], (e.num_experts, d, f)) * s_in).astype(dtype),
        "w_gate": (jax.random.normal(ks[2], (e.num_experts, d, f)) * s_in).astype(dtype),
        "w_out": (jax.random.normal(ks[3], (e.num_experts, f, d)) * s_out).astype(dtype),
    }
    if e.dense_residual_ff:
        from repro.models.layers import mlp_init
        p["dense"] = mlp_init(ks[4], d, e.dense_residual_ff, cfg.activation, dtype)
    return p


def capacity(tokens: int, e: MoEConfig) -> int:
    c = int(np.ceil(tokens * e.top_k / e.num_experts * e.capacity_factor))
    return max(8, -(-c // 8) * 8)  # round up to 8 for TPU lane alignment


def _constrain(t, spec):
    from repro.parallel.sharding import maybe_constrain
    return maybe_constrain(t, spec)


def moe_ffn_shardmap(p, x, cfg: ModelConfig, rt):
    """Expert-parallel MoE via shard_map (the TPU-native dispatch).

    Device (i, j) — data shard i, model shard j — already holds data shard
    i's activations replicated over j, so dispatch is a LOCAL masked gather
    of the tokens routed to j's experts (capacity budgeted per data shard,
    as real EP systems do). Expert weights stream in with an explicit
    all-gather over the data axes (ZeRO-3), and outputs combine with one
    psum over `model`. No global scatter ever hits the SPMD partitioner —
    XLA's auto-dispatch replicated multi-GB (T*K, d) buffers on every
    device (measured: +6.5 GB/device on arctic-480b).
    """
    from jax.sharding import PartitionSpec as P
    e = cfg.moe
    B, S, d = x.shape
    T = B * S
    E, K = e.num_experts, e.top_k
    dp = tuple(rt.mesh_batch_axes)
    dp_size = rt.dp_size
    T_loc = T // max(dp_size, 1)
    C_loc = max(8, -(-int(np.ceil(T_loc * K / E * e.capacity_factor)) // 8) * 8)

    xf = x.reshape(T, d)
    logits = (xf.astype(jnp.float32) @ p["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, K)
    gate_vals = (gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True),
                                         1e-9)).astype(x.dtype)

    ep_axes = tuple(getattr(rt, "ep_axes", ("model",)))

    def local_fn(xl, eidx, gates, w_in, w_gate, w_out):
        E_loc = w_in.shape[0]
        # combined expert-shard index over the (possibly multi-axis) EP axes
        j = jnp.int32(0)
        for a in ep_axes:
            j = j * axis_size(a) + jax.lax.axis_index(a)
        lo = j * E_loc
        if dp:  # ZeRO-3: stream the full expert weights for this model shard
            w_in = jax.lax.all_gather(w_in, dp, axis=1, tiled=True)
            w_gate = jax.lax.all_gather(w_gate, dp, axis=1, tiled=True)
            w_out = jax.lax.all_gather(w_out, dp, axis=2, tiled=True)
        t_loc = xl.shape[0]
        flat_e = eidx.reshape(-1)
        flat_g = gates.reshape(-1)
        tok = jnp.repeat(jnp.arange(t_loc), K)
        rel = jnp.where((flat_e >= lo) & (flat_e < lo + E_loc),
                        flat_e - lo, E_loc)
        order = jnp.argsort(rel)
        se, sg, st = rel[order], flat_g[order], tok[order]
        seg_start = jnp.searchsorted(se, jnp.arange(E_loc), side="left")
        pos = jnp.arange(t_loc * K) - seg_start[jnp.minimum(se, E_loc - 1)]
        keep = (se < E_loc) & (pos < C_loc)
        dest = jnp.where(keep, se * C_loc + pos, E_loc * C_loc)
        buf = jnp.zeros((E_loc * C_loc + 1, d), xl.dtype)
        buf = buf.at[dest].set(jnp.where(keep[:, None], xl[st], 0))
        buf = buf[: E_loc * C_loc].reshape(E_loc, C_loc, d)
        h = jnp.einsum("ecd,edf->ecf", buf, w_in)
        if cfg.activation == "swiglu":
            h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, w_gate)) * h
        else:
            h = jax.nn.gelu(h, approximate=True)
        ob = jnp.einsum("ecf,efd->ecd", h, w_out).reshape(E_loc * C_loc, d)
        y_rows = ob[jnp.where(keep, dest, 0)] * (sg * keep)[:, None].astype(xl.dtype)
        y = jnp.zeros((t_loc, d), xl.dtype).at[st].add(y_rows)
        y = jax.lax.psum(y, ep_axes)
        load = jnp.zeros((E_loc,)).at[jnp.minimum(se, E_loc - 1)].add(
            keep.astype(jnp.float32))
        if dp:
            load = jax.lax.psum(load, dp)
        return y, load

    dps = dp if dp else None
    eps = ep_axes if len(ep_axes) > 1 else ep_axes[0]
    y, load = shard_map(
        local_fn,
        in_specs=(P(dps, None), P(dps, None), P(dps, None),
                  P(eps, dps, None), P(eps, dps, None),
                  P(eps, None, dps)),
        out_specs=(P(dps, None), P(eps)),
        check_vma=False,
    )(xf, expert_idx, gate_vals, p["w_in"], p["w_gate"], p["w_out"])
    y = y.reshape(B, S, d)

    if e.dense_residual_ff:
        from repro.models.layers import apply_mlp
        y = y + apply_mlp(p["dense"], x, cfg.activation)

    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(jax.nn.one_hot(expert_idx[:, 0], E), axis=0)
    aux_loss = E * jnp.sum(me * ce) * e.router_aux_weight
    aux = {"aux_loss": aux_loss, "expert_load": load, "capacity": C_loc}
    return y, aux


def moe_ffn(p, x, cfg: ModelConfig, rt=None):
    if rt is not None and getattr(rt, "moe_shardmap", False):
        return moe_ffn_shardmap(p, x, cfg, rt)
    return _moe_ffn_dense(p, x, cfg, rt)


def _moe_ffn_dense(p, x, cfg: ModelConfig, rt=None):
    """x: (B, S, d) -> (out, aux) where aux has the router load stats used by
    the Tier-1 load-imbalance metric and the aux loss.

    Sharding: token-major tensors (T*K, d) shard rows over the batch axes;
    expert-capacity buffers (E, C, d) shard E over `model` (aligned with the
    expert weights) — without these constraints XLA replicates multi-GB
    dispatch buffers on every device."""
    from jax.sharding import PartitionSpec as P
    tok_spec = cap_spec = None
    if rt is not None and rt.act_spec is not None and rt.act_spec[0] is not None:
        tok_spec = P(rt.act_spec[0], None)
        cap_spec = P("model", None, None)
    e = cfg.moe
    B, S, d = x.shape
    T = B * S
    xf = x.reshape(T, d)
    E, K = e.num_experts, e.top_k
    C = capacity(T, e)

    logits = (xf.astype(jnp.float32) @ p["router"])            # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, K)            # (T, K)
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)

    # ---- flatten (token, k) pairs and sort by expert ----------------------
    flat_expert = expert_idx.reshape(-1)                       # (T*K,)
    flat_gate = gate_vals.reshape(-1)
    flat_token = jnp.repeat(jnp.arange(T), K)
    order = jnp.argsort(flat_expert)
    se, sg, st = flat_expert[order], flat_gate[order], flat_token[order]
    # position within expert segment
    seg_start = jnp.searchsorted(se, jnp.arange(E), side="left")  # (E,)
    pos = jnp.arange(T * K) - seg_start[se]
    keep = pos < C
    dest = jnp.where(keep, se * C + pos, E * C)                # overflow slot

    # ---- gather into capacity buffers -------------------------------------
    rows_in = _constrain(jnp.where(keep[:, None], xf[st], 0), tok_spec)
    buf = jnp.zeros((E * C + 1, d), x.dtype)
    buf = buf.at[dest].set(rows_in)
    buf = _constrain(buf[: E * C].reshape(E, C, d), cap_spec)

    # ---- expert FFN (E sharded over model axis) ----------------------------
    h = jnp.einsum("ecd,edf->ecf", buf, p["w_in"])
    if cfg.activation == "swiglu":
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, p["w_gate"])) * h
    else:
        h = jax.nn.gelu(h, approximate=True)
    out_buf = jnp.einsum("ecf,efd->ecd", h, p["w_out"])
    out_buf = _constrain(out_buf, cap_spec).reshape(E * C, d)

    # ---- combine back -------------------------------------------------------
    rows = jnp.where(keep, dest, 0)
    y_rows = out_buf[rows] * (sg * keep)[:, None].astype(x.dtype)
    y_rows = _constrain(y_rows, tok_spec)
    y = jnp.zeros((T, d), x.dtype).at[st].add(y_rows)
    y = y.reshape(B, S, d)

    if e.dense_residual_ff:
        from repro.models.layers import apply_mlp
        y = y + apply_mlp(p["dense"], x, cfg.activation)

    # ---- aux: load-balance loss (Switch) + per-expert load -----------------
    me = jnp.mean(probs, axis=0)                               # router prob mass
    one_hot_top1 = jax.nn.one_hot(expert_idx[:, 0], E)
    ce = jnp.mean(one_hot_top1, axis=0)                        # fraction routed
    aux_loss = E * jnp.sum(me * ce) * e.router_aux_weight
    expert_load = jnp.zeros((E,)).at[se].add(keep.astype(jnp.float32))
    aux = {"aux_loss": aux_loss, "expert_load": expert_load, "capacity": C}
    return y, aux
