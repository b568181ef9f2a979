"""Sharding rules: map every parameter/cache/batch leaf to a PartitionSpec.

Two execution modes (see DESIGN.md — they mirror the paper's Cerebras
whole-graph-resident vs weight-streaming modes):

* ``resident``  — weights sharded over `model` only (TP); replicated over the
  data axes. No per-layer gathers; highest memory.
* ``streaming`` — FSDP x TP: weights additionally shard their contraction dim
  over `data` (ZeRO-3). XLA all-gathers each layer's weights inside the layer
  scan = the TPU-idiomatic analogue of weight streaming.

Heads that don't divide the model axis (rwkv6 d->H*hs reshape, hymba SSD
heads=25) keep their projections replicated over `model`; the Tier-1
allocation-ratio metric surfaces exactly this idle-axis effect.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
from jax.sharding import PartitionSpec as P

from repro.configs.base import MeshConfig, ModelConfig, RunConfig


def batch_axes(mesh_cfg: MeshConfig) -> Tuple[str, ...]:
    return mesh_cfg.data_axes  # ('pod','data') or ('data',)


def have_ambient_mesh() -> bool:
    try:
        m = jax.sharding.get_abstract_mesh()
        return m is not None and not m.empty
    except Exception:
        return False


def maybe_constrain(x, spec):
    """with_sharding_constraint that degrades to a no-op outside any mesh
    context (single-device smoke tests)."""
    if spec is None or not have_ambient_mesh():
        return x
    return jax.lax.with_sharding_constraint(x, spec)


def batch_spec(mesh_cfg: MeshConfig, global_batch: int,
               exclude: Tuple[str, ...] = ()) -> Optional[Tuple]:
    """Axes to shard the batch dim over, honoring divisibility. `exclude`
    removes axes repurposed elsewhere (e.g. 'pod' under EP-over-pod)."""
    axes = []
    size = 1
    for a in batch_axes(mesh_cfg):
        if a in exclude:
            continue
        s = dict(zip(mesh_cfg.axes, mesh_cfg.shape))[a]
        if global_batch % (size * s) == 0:
            axes.append(a)
            size *= s
    return tuple(axes) if axes else None


def act_pspec(mesh_cfg: MeshConfig, global_batch: int,
              exclude: Tuple[str, ...] = ()) -> P:
    """(B, S, d) activation spec."""
    return P(batch_spec(mesh_cfg, global_batch, exclude), None, None)


def _divisible(n: int, mesh_cfg: MeshConfig, axis: str) -> bool:
    return n % dict(zip(mesh_cfg.axes, mesh_cfg.shape))[axis] == 0


def param_pspecs(params_shape, cfg: ModelConfig, rcfg: RunConfig):
    """PartitionSpec pytree matching the params pytree (built from shapes so
    it works on ShapeDtypeStructs)."""
    mesh_cfg = rcfg.mesh
    fsdp = "data" if rcfg.exec_mode == "streaming" else None

    def rule(path, leaf):
        names = [getattr(p, "key", getattr(p, "name", "")) for p in path]
        name = names[-1]
        in_moe = "moe" in names and "dense" not in names
        in_ssm = "time_mix" in names or "channel_mix" in names or "ssm" in names
        stacked = names[0] in ("layers", "enc_layers")
        L = (None,) if stacked else ()
        nd = len(leaf.shape)

        def spec(*rest):
            if len(L) + len(rest) != nd:
                raise ValueError(
                    f"partition spec rank mismatch for {names}: leaf shape "
                    f"{leaf.shape} vs spec {L + rest}")
            return P(*L, *rest)

        # ---- embedding ----
        if names[0] == "embed":
            if name == "tok":
                vshard = "model" if _divisible(leaf.shape[0], mesh_cfg,
                                               "model") else None
                return P(vshard, fsdp)
            if name == "head":
                vshard = "model" if _divisible(leaf.shape[1], mesh_cfg,
                                               "model") else None
                return P(fsdp, vshard)
        # ---- norms / scalars / vectors ----
        if nd - len(L) <= 1 or name in ("mix", "mix_b", "u", "ln_scale",
                                        "ln_bias", "w0", "dt_bias", "A_log",
                                        "D"):
            if name in ("bq", "bk", "bv") and not in_ssm:
                return spec("model")
            return P(*((None,) * nd))
        # ---- MoE ----
        if in_moe:
            ep = ("pod", "model") if getattr(rcfg, "ep_over_pod", False) \
                else "model"
            if name == "router":
                return spec(fsdp, None)
            if name in ("w_in", "w_gate"):
                return spec(ep, fsdp, None)          # (E, d, f): EP sharding
            if name == "w_out":
                return spec(ep, None, fsdp)
        # ---- rwkv6 time/channel mix + hymba ssd: heads don't divide the
        #      model axis -> replicate over model, FSDP over data ----
        if in_ssm:
            if getattr(rcfg, "ssm_tp", False) and "time_mix" in names:
                if name in ("wr", "wk", "wv", "wg") and _divisible(
                        leaf.shape[-1], mesh_cfg, "model"):
                    return spec(fsdp, "model")   # TP; XLA reshards for wkv
                if name == "wo" and _divisible(leaf.shape[-2], mesh_cfg,
                                               "model"):
                    return spec("model", fsdp)
            if "channel_mix" in names:
                if name == "wk" and _divisible(leaf.shape[-1], mesh_cfg,
                                               "model"):
                    return spec(fsdp, "model")     # (d, f) TP on f
                if name == "wv":
                    if _divisible(leaf.shape[-2], mesh_cfg, "model"):
                        return spec("model", None)  # (f, d) contraction TP
                    return spec(None, fsdp)
                return spec(fsdp, None)
            if name == "wo":
                return spec(None, fsdp)
            if name == "wb":
                return spec(None, None)
            return spec(fsdp, None)  # wr/wk/wv/wg/wx/wz/wB/wC/wdt/wa
        # ---- attention ----
        if name in ("wq", "wk", "wv"):
            return spec(fsdp, "model")
        if name == "wo":
            return spec("model", fsdp)
        # ---- dense mlp ----
        if name in ("w_in", "w_gate"):
            ok = _divisible(leaf.shape[-1], mesh_cfg, "model")
            return spec(fsdp, "model" if ok else None)
        if name == "w_out":
            ok = _divisible(leaf.shape[-2], mesh_cfg, "model")
            return spec("model" if ok else None, fsdp)
        return P(*((None,) * nd))

    def guarded(path, leaf):
        # universal divisibility guard: drop axes a dim can't divide
        # (e.g. d_model=1600 over data=128 on extreme mesh splits)
        return _fit_spec(rule(path, leaf), leaf.shape, mesh_cfg)

    return jax.tree_util.tree_map_with_path(guarded, params_shape)


def _fit_spec(spec: P, shape, mesh_cfg: MeshConfig) -> P:
    """Drop sharding on dims the shape can't divide evenly."""
    sizes = dict(zip(mesh_cfg.axes, mesh_cfg.shape))
    out = []
    for i, entry in enumerate(spec):
        if entry is None or i >= len(shape):
            out.append(entry)
            continue
        axes = entry if isinstance(entry, tuple) else (entry,)
        total = 1
        for a in axes:
            total *= sizes.get(a, 1)
        out.append(entry if shape[i] % total == 0 else None)
    return P(*out)


def opt_state_shardings(opt_shape, params_pspecs, mesh, mesh_cfg: MeshConfig):
    """Shardings for an AdamWState whose m/v may hold Q8(q, scale) nodes.
    q inherits the param's spec; scale (rank-preserving, last dim /8) gets
    the same spec with divisibility fallback."""
    from jax.sharding import NamedSharding
    from jax.tree_util import keystr, tree_flatten_with_path, \
        tree_map_with_path

    from repro.optim.adamw import AdamWState

    flat, _ = tree_flatten_with_path(
        params_pspecs, is_leaf=lambda x: isinstance(x, P))
    by_path = {keystr(p): s for p, s in flat}

    def rule(path, leaf):
        ks = keystr(path)
        for suffix in (".q", ".scale"):
            if ks.endswith(suffix):
                ks = ks[: -len(suffix)]
        spec = by_path.get(ks, P(*((None,) * leaf.ndim)))
        if len(spec) != leaf.ndim:
            spec = P(*(tuple(spec) + (None,) * leaf.ndim)[: leaf.ndim])
        return NamedSharding(mesh, _fit_spec(spec, leaf.shape, mesh_cfg))

    m = tree_map_with_path(rule, opt_shape.m)
    v = tree_map_with_path(rule, opt_shape.v)
    master = tree_map_with_path(rule, opt_shape.master)
    return AdamWState(step=NamedSharding(mesh, P()), master=master,
                      m=m, v=v)


def cache_pspecs(caches_shape, cfg: ModelConfig, rcfg: RunConfig,
                 global_batch: int):
    """Decode-cache specs: batch over data axes; full-attention KV caches and
    cross caches shard their sequence dim over `model` (paired with the
    lse-combining partitioned decode attention)."""
    mesh_cfg = rcfg.mesh
    bspec = batch_spec(mesh_cfg, global_batch)

    def rule(path, leaf):
        names = [getattr(p, "key", getattr(p, "name", "")) for p in path]
        name = names[-1]
        if name in ("k", "v", "ck", "cv"):
            seq = leaf.shape[2]
            seq_shard = ("model" if cfg.attention_kind != "sliding"
                         and rcfg.decode_attention == "partitioned"
                         and _divisible(seq, mesh_cfg, "model") else None)
            return P(None, bspec, seq_shard, None, None)
        # states: (L, B, H, *, *)
        return P(None, bspec, *((None,) * (len(leaf.shape) - 2)))

    return jax.tree_util.tree_map_with_path(rule, caches_shape)
