"""Weights of the rwkv6 configuration made from ``--seed``, the same for
the program and the reference.

Every leaf is drawn from its own key, as :mod:`weights` draws granite's
(``fold_in(fold_in(key(seed), leaf), layer)``, with that module's seed
words and keys), so that one layer's slice of a leaf can be made again
alone. The leaves are laid out as the program's rwkv6 model holds them
(stacked over layers); the harness checks that layout against the
program's own ``init_params`` by shape.

Finch's per-channel schedules (RWKV-LM's ``x060`` initialisation) set the
static token-shift mixes, the base decay ``w0`` and the bonus ``u`` at the
layer ids held: the chip holds layers ``first_layer`` onwards of the
published depth. The adapters' factors, zero or near zero in that
initialisation, are drawn at scales at which the data-dependent shift and
decay move the result.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

import weights as W

MIXES = ("w", "k", "v", "r", "g")      # the program's order of the mixes


def dims(cfg: dict) -> dict:
    """Sizes of the rwkv6 configuration file."""
    d, hs = cfg["hidden_size"], cfg["head_size"]
    return dict(d=d, hs=hs, H=cfg["attention_hidden_size"] // hs,
                f=cfg["intermediate_size"] or int(d * 3.5) // 32 * 32,
                L=cfg["num_hidden_layers"], V=cfg["vocab_size"],
                pv=-(-cfg["vocab_size"] // W.VOCAB_PAD) * W.VOCAB_PAD,
                R=cfg["mix_rank"], Rd=cfg["decay_rank"])


def leaf_specs(cfg: dict, dtype: str) -> list:
    """(path, shape, dtype, scale, stacked) for every leaf. A scale is a
    normal's standard deviation, "norm" (a gain around 1), "bias" (a
    small offset around 0) or the name of one of Finch's schedules."""
    m = dims(cfg)
    d, f, H, hs, L, R, Rd = (m["d"], m["f"], m["H"], m["hs"], m["L"],
                             m["R"], m["Rd"])
    f32 = "float32"
    tm, cm = "layers/time_mix/", "layers/channel_mix/"
    return [
        ("embed/tok", (m["pv"], d), dtype, 1.0, False),
        ("embed/head", (d, m["pv"]), dtype, d ** -0.5, False),
        ("ln0/scale", (d,), f32, "norm", False),
        ("ln0/bias", (d,), f32, "bias", False),
        ("layers/norm1/scale", (L, d), f32, "norm", True),
        ("layers/norm1/bias", (L, d), f32, "bias", True),
        ("layers/norm2/scale", (L, d), f32, "norm", True),
        ("layers/norm2/bias", (L, d), f32, "bias", True),
        (tm + "mix_x", (L, d), f32, "mix_x", True),
        (tm + "mix", (L, len(MIXES), d), f32, "mix", True),
        (tm + "mix_a", (L, d, len(MIXES) * R), dtype, d ** -0.5, True),
        (tm + "mix_b", (L, len(MIXES), R, d), dtype, 0.1, True),
        (tm + "wr", (L, d, d), dtype, d ** -0.5, True),
        (tm + "wk", (L, d, d), dtype, d ** -0.5, True),
        (tm + "wv", (L, d, d), dtype, d ** -0.5, True),
        (tm + "wg", (L, d, d), dtype, d ** -0.5, True),
        (tm + "wo", (L, d, d), dtype, d ** -0.5, True),
        (tm + "w0", (L, d), f32, "decay", True),
        (tm + "wa", (L, d, Rd), dtype, d ** -0.5, True),
        (tm + "wb", (L, Rd, d), dtype, 0.1, True),
        (tm + "u", (L, H, hs), f32, "bonus", True),
        (tm + "ln_scale", (L, H, hs), f32, "norm", True),
        (tm + "ln_bias", (L, H, hs), f32, "bias", True),
        (cm + "mix", (L, 2, d), f32, "channel_mix", True),
        (cm + "wk", (L, d, f), dtype, d ** -0.5, True),
        (cm + "wv", (L, f, d), dtype, f ** -0.5, True),
        (cm + "wr", (L, d, d), dtype, d ** -0.5, True),
        ("final_norm/scale", (d,), f32, "norm", False),
        ("final_norm/bias", (d,), f32, "bias", False),
    ]


def schedule(name: str, layer, shape, depth: int):
    """Finch's initial value of a per-channel leaf at layer id ``layer``
    of ``depth`` (RWKV-LM ``x060``): ``ramp`` is channel / width, ``r01``
    the layer's place from 0 to 1 and ``r1`` from 1 towards 0."""
    n = shape[-1] if name != "bonus" else shape[0] * shape[1]
    i = jnp.arange(n, dtype=jnp.float32)
    ramp, last = i / n, i / (n - 1)
    r01 = layer / (depth - 1)
    r1 = 1.0 - layer / depth
    if name == "mix_x":
        return 1 - ramp ** r1
    if name == "mix":                       # w, k, v, r, g
        return jnp.stack([1 - ramp ** r1, 1 - ramp ** r1,
                          1 - (ramp ** r1 + 0.3 * r01),
                          1 - ramp ** (0.5 * r1), 1 - ramp ** (0.5 * r1)])
    if name == "channel_mix":               # k, r
        return jnp.stack([1 - ramp ** r1] * 2)
    if name == "decay":
        return -6 + 5 * last ** (0.7 + 1.3 * r01)
    if name == "bonus":
        zigzag = ((i + 1) % 3 - 1) * 0.1
        return (r01 * (1 - last) + zigzag).reshape(shape)
    raise KeyError(name)


def _draw(key, layer, shape, dtype, scale, depth: int):
    if scale == "norm":
        x = 1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)
    elif scale == "bias":
        x = 0.1 * jax.random.normal(key, shape, jnp.float32)
    elif isinstance(scale, str):
        x = schedule(scale, layer, shape, depth)
    else:
        x = jax.random.normal(key, shape, jnp.float32) * scale
    return x.astype(dtype)


def _placement(cfg: dict) -> tuple:
    """(published depth, id of the first layer held)."""
    return cfg["published"]["num_hidden_layers"], cfg["first_layer"]


def make_params(cfg: dict, seed: int, dtype: str, shardings=None):
    """All leaves in the program's layout, made on the device in one jitted
    call. ``shardings`` (a matching tree) places them across chips."""
    specs = leaf_specs(cfg, dtype)
    depth, first = _placement(cfg)

    def leaf(words, index, shape, dt, scale, stacked):
        key = W._leaf_key(words, index)
        if not stacked:
            return _draw(key, first, shape, dt, scale, depth)
        return jax.vmap(lambda l: _draw(jax.random.fold_in(key, l),
                                        first + l, shape[1:], dt, scale,
                                        depth))(jnp.arange(shape[0]))

    def build(words):
        return W._nest({p: leaf(words, i, s, t, sc, st)
                        for i, (p, s, t, sc, st) in enumerate(specs)})

    return jax.jit(build, out_shardings=shardings)(W.seed_words(seed))


@partial(jax.jit, static_argnums=(2, 3, 4, 5, 6, 7, 8))
def _one(words, layer, index, shape, dtype, scale, stacked, first, depth):
    key = W._leaf_key(words, index)
    if stacked:
        key = jax.random.fold_in(key, layer)
    return _draw(key, first + layer, shape, dtype, scale, depth)


def layer_leaf(cfg: dict, seed: int, dtype: str, path: str, layer: int):
    """One layer's slice of a stacked leaf, equal to what ``make_params``
    put there; a leaf that is not stacked comes whole (``layer`` ignored)."""
    depth, first = _placement(cfg)
    for i, (p, shape, dt, scale, stacked) in enumerate(leaf_specs(cfg,
                                                                  dtype)):
        if p == path:
            return _one(W.seed_words(seed), jnp.int32(layer if stacked
                                                       else 0), i,
                        shape[1:] if stacked else shape, dt, scale, stacked,
                        first, depth)
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
