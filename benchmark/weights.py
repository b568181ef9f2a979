"""Weights made from ``--seed``, the same for the program and the reference.

Every leaf is drawn from its own key, ``fold_in(fold_in(key(seed), leaf),
layer)``, so the reference can make one layer's weights at a time and get
exactly what the program was given for that layer. The leaves are laid
out as the program's dense decoder holds them (stacked over layers); the
harness checks that layout against the program's own ``init_params`` by
shape, and fails loudly where the program changed it.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

VOCAB_PAD = 256          # rows of the embedding are padded to a multiple


def dims(cfg: dict) -> dict:
    """Sizes of a dense GQA decoder from its configuration file."""
    d = cfg["hidden_size"]
    hq = cfg["num_attention_heads"]
    return dict(d=d, f=cfg["intermediate_size"], hq=hq,
                hkv=cfg["num_key_value_heads"],
                hd=cfg.get("head_dim") or d // hq,
                L=cfg["num_hidden_layers"], V=cfg["vocab_size"],
                pv=-(-cfg["vocab_size"] // VOCAB_PAD) * VOCAB_PAD)


def leaf_specs(cfg: dict, dtype: str) -> list:
    """(path, shape, dtype, scale, stacked) for every leaf; scale "norm"
    draws a norm gain around 1."""
    m = dims(cfg)
    d, f, hq, hkv, hd, L = m["d"], m["f"], m["hq"], m["hkv"], m["hd"], m["L"]
    return [
        ("embed/tok", (m["pv"], d), dtype, 0.02, False),
        ("layers/norm1/scale", (L, d), "float32", "norm", True),
        ("layers/norm2/scale", (L, d), "float32", "norm", True),
        ("layers/attn/wq", (L, d, hq * hd), dtype, d ** -0.5, True),
        ("layers/attn/wk", (L, d, hkv * hd), dtype, d ** -0.5, True),
        ("layers/attn/wv", (L, d, hkv * hd), dtype, d ** -0.5, True),
        ("layers/attn/wo", (L, hq * hd, d), dtype, (hq * hd) ** -0.5, True),
        ("layers/mlp/w_gate", (L, d, f), dtype, d ** -0.5, True),
        ("layers/mlp/w_in", (L, d, f), dtype, d ** -0.5, True),
        ("layers/mlp/w_out", (L, f, d), dtype, f ** -0.5, True),
        ("final_norm/scale", (d,), "float32", "norm", False),
    ]


def _draw(key, shape, dtype, scale):
    x = jax.random.normal(key, shape, jnp.float32)
    x = 1.0 + 0.1 * x if scale == "norm" else x * scale
    return x.astype(dtype)


def seed_words(seed: int):
    """A seed of any size as two 32-bit words, passed to jit as data so
    that one compiled program serves every seed."""
    return jnp.asarray([seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF],
                       jnp.uint32)


def _leaf_key(words, index: int):
    key = jax.random.fold_in(jax.random.PRNGKey(words[0]), words[1])
    return jax.random.fold_in(key, index)


def _leaf(seed, index, shape, dtype, scale, stacked):
    key = _leaf_key(seed, index)
    if not stacked:
        return _draw(key, shape, dtype, scale)
    keys = jax.vmap(lambda l: jax.random.fold_in(key, l))(
        jnp.arange(shape[0]))
    return jax.vmap(lambda k: _draw(k, shape[1:], dtype, scale))(keys)


def _nest(flat: dict) -> dict:
    out: dict = {}
    for path, v in flat.items():
        node = out
        *parents, last = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[last] = v
    return out


def make_params(cfg: dict, seed: int, dtype: str, shardings=None):
    """All leaves in the program's layout, made on the device in one jitted
    call. ``shardings`` (a matching tree) places them across chips."""
    specs = leaf_specs(cfg, dtype)

    def build(words):
        return _nest({p: _leaf(words, i, s, t, sc, st)
                      for i, (p, s, t, sc, st) in enumerate(specs)})

    return jax.jit(build, out_shardings=shardings)(seed_words(seed))


@partial(jax.jit, static_argnums=(2, 3, 4, 5))
def _layer_leaf(words, layer, index, shape, dtype, scale):
    key = jax.random.fold_in(_leaf_key(words, index), layer)
    return _draw(key, shape, dtype, scale)


@partial(jax.jit, static_argnums=(1, 2, 3, 4))
def _whole_leaf(words, index, shape, dtype, scale):
    return _draw(_leaf_key(words, index), shape, dtype, scale)


def layer_leaf(cfg: dict, seed: int, dtype: str, path: str, layer: int):
    """One layer's slice of a stacked leaf, equal to what ``make_params``
    put there; a leaf that is not stacked comes whole (``layer`` ignored)."""
    for i, (p, shape, dt, scale, stacked) in enumerate(leaf_specs(cfg, dtype)):
        if p == path:
            if not stacked:
                return _whole_leaf(seed_words(seed), i, shape, dt, scale)
            return _layer_leaf(seed_words(seed), jnp.int32(layer), i,
                               shape[1:], dt, scale)
    raise KeyError(path)


def check_layout(params_shape, cfg: dict, dtype: str) -> None:
    """Raise where the program's parameter tree differs from ours."""
    want = {p: (tuple(s), jnp.dtype(t)) for p, s, t, _, _ in
            leaf_specs(cfg, dtype)}
    got = {"/".join(str(getattr(k, "key", k)) for k in path):
           (tuple(leaf.shape), jnp.dtype(leaf.dtype))
           for path, leaf in jax.tree_util.tree_flatten_with_path(
               params_shape)[0]}
    if got != want:
        raise RuntimeError(
            "the program's parameter layout is not the one the benchmark "
            f"makes weights for: program {sorted(got.items())} vs benchmark "
            f"{sorted(want.items())}")


def param_count(cfg: dict) -> dict:
    """Parameters of the matrices a token passes through, for FLOP counts:
    per layer, and of the tied head over the published vocabulary."""
    m = dims(cfg)
    d, f, hq, hkv, hd = m["d"], m["f"], m["hq"], m["hkv"], m["hd"]
    per_layer = d * hq * hd + 2 * d * hkv * hd + hq * hd * d + 3 * d * f
    return {"layer": per_layer, "head": d * m["V"],
            "layers": per_layer * m["L"]}
