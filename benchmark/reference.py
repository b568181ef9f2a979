"""Plain reference of the dense GQA decoder the configurations describe.

Written from the published description of granite-3.0 (a Llama-style
decoder: RMSNorm, rotary positions with the rotate-half convention, grouped
query attention, SwiGLU, tied embeddings, and granite's four multipliers,
taken from the configuration file as it is run). It imports nothing of the
program. It computes in float32 with matmuls at the highest precision, one
layer at a time, each layer's weights made afresh from the seed
(:mod:`weights`), so that it fits on one chip after the program has left.

``precision="fp8"`` is the control: every matmul input is rounded to
float8 (e4m3) first, the step below the bfloat16 that the configurations
state (in training the gradient passes through the rounding unchanged).
It must fail the comparison that the program passes.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

import weights as W

F32 = jnp.float32


def _q(x, precision: str):
    """``x`` rounded to the control's precision in the forward pass; the
    gradient passes through unrounded."""
    if precision == "fp8":
        low = x.astype(jnp.float8_e4m3fn).astype(F32)
        return x + jax.lax.stop_gradient(low - x)
    return x


def _mm(a, b, precision: str):
    return jnp.matmul(_q(a, precision), _q(b, precision),
                      precision=jax.lax.Precision.HIGHEST)


def _rms(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * gain


def _rotate(x, positions, theta):
    """HF-style rotary embedding: x * cos + rotate_half(x) * sin."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd)
    ang = positions[:, None].astype(F32) * inv[None]          # (S, hd/2)
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None]
    half = hd // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos + rot * sin


def _attention(q, k, v, positions, scale, groups, precision, block=512):
    """Causal attention in blocks of ``block`` queries, each recomputed in
    the backward pass, so that no (S, S) score matrix is ever whole."""
    k = jnp.repeat(k, groups, axis=2)
    v = jnp.repeat(v, groups, axis=2)

    @jax.checkpoint
    def one(qb, pb):
        s = jnp.einsum("bqhd,bkhd->bhqk", _q(qb, precision), _q(k, precision),
                       precision=jax.lax.Precision.HIGHEST) * scale
        s = jnp.where(pb[:, None] >= positions[None, :], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", _q(p, precision),
                          _q(v, precision),
                          precision=jax.lax.Precision.HIGHEST)

    S = q.shape[1]
    return jnp.concatenate([one(q[:, i:i + block], positions[i:i + block])
                            for i in range(0, S, block)], axis=1)


def layer_forward(w: dict, x, positions, cfg: dict, precision: str = "f32"):
    """One decoder layer over a whole sequence x (B, S, d), causal."""
    m = W.dims(cfg)
    hq, hkv, hd = m["hq"], m["hkv"], m["hd"]
    eps, res = cfg["rms_norm_eps"], cfg["residual_multiplier"]
    B, S, _ = x.shape
    h = _rms(x, w["norm1"], eps)
    q = _mm(h, w["wq"], precision).reshape(B, S, hq, hd)
    k = _mm(h, w["wk"], precision).reshape(B, S, hkv, hd)
    v = _mm(h, w["wv"], precision).reshape(B, S, hkv, hd)
    q = _rotate(q, positions, cfg["rope_theta"])
    k = _rotate(k, positions, cfg["rope_theta"])
    o = _attention(q, k, v, positions, cfg["attention_multiplier"],
                   hq // hkv, precision)
    x = x + res * _mm(o.reshape(B, S, hq * hd), w["wo"], precision)
    h = _rms(x, w["norm2"], eps)
    a = _mm(h, w["w_gate"], precision)
    u = _mm(h, w["w_in"], precision)
    x = x + res * _mm(jax.nn.silu(a) * u, w["w_out"], precision)
    return x


def embed(tok, ids, cfg: dict):
    return tok[ids].astype(F32) * cfg["embedding_multiplier"]


def head(x, final_gain, tok, cfg: dict, precision: str = "f32"):
    """Logits over the published vocabulary (the padded rows are not part
    of the model)."""
    x = _rms(x, final_gain, cfg["rms_norm_eps"])
    V = cfg["vocab_size"]
    return _mm(x, tok[:V].astype(F32).T, precision) / cfg["logits_scaling"]


LAYER_LEAVES = {"norm1": "layers/norm1/scale", "norm2": "layers/norm2/scale",
                "wq": "layers/attn/wq", "wk": "layers/attn/wk",
                "wv": "layers/attn/wv", "wo": "layers/attn/wo",
                "w_gate": "layers/mlp/w_gate", "w_in": "layers/mlp/w_in",
                "w_out": "layers/mlp/w_out"}


def layer_weights(cfg: dict, seed: int, dtype: str, layer: int) -> dict:
    """Layer ``layer``'s weights in float32, as the program holds them."""
    return {k: W.layer_leaf(cfg, seed, dtype, p, layer).astype(F32)
            for k, p in LAYER_LEAVES.items()}


_layer_jit = jax.jit(layer_forward, static_argnums=(3, 4))
_head_jit = jax.jit(head, static_argnums=(3, 4))


def _frozen(cfg: dict):
    """A hashable copy of the configuration, for jit's static arguments."""
    return _Frozen(cfg)


class _Frozen(dict):
    def __hash__(self):
        return hash(tuple(sorted((k, repr(v)) for k, v in self.items())))


def sequence_logits(cfg: dict, seed: int, dtype: str, ids: np.ndarray,
                    precision: str = "f32", bucket: int = 512):
    """Reference logits (S, V) for every position of each token row
    ``ids`` (B, S). Rows are padded at the end to a multiple of ``bucket``
    (causal attention never looks ahead, so padding changes nothing before
    it) so that few shapes compile."""
    fz = _frozen(cfg)
    B, S = ids.shape
    Sp = -(-S // bucket) * bucket
    ids_p = np.zeros((B, Sp), np.int32)
    ids_p[:, :S] = ids
    tok = W.layer_leaf(cfg, seed, dtype, "embed/tok", 0)
    x = embed(tok, jnp.asarray(ids_p), cfg)
    positions = jnp.arange(Sp)
    for layer in range(cfg["num_hidden_layers"]):
        x = _layer_jit(layer_weights(cfg, seed, dtype, layer), x, positions,
                       fz, precision)
    gain = W.layer_leaf(cfg, seed, dtype, "final_norm/scale", 0)
    return _head_jit(x, gain, tok, fz, precision)[:, :S]


@partial(jax.jit, static_argnums=())
def served_gaps(logits, served):
    """How far below the reference's best logit each served token's logit
    lies: (B, S) for logits (B, S, V) and served ids (B, S)."""
    best = logits.max(-1)
    got = jnp.take_along_axis(logits, served[..., None], -1)[..., 0]
    return best - got


# ------------------------------------------------------------- training
def _layers_view(params):
    """The stacked per-layer leaves under the names ``layer_forward`` uses."""
    return {k: _get(params, p) for k, p in LAYER_LEAVES.items()}


def _get(tree, path: str):
    for k in path.split("/"):
        tree = tree[k]
    return tree


def loss(params, tokens, labels, cfg: dict, precision: str = "f32"):
    """Mean next-token cross-entropy over every position of the batch,
    with each layer recomputed in the backward pass."""
    positions = jnp.arange(tokens.shape[1])
    tok = params["embed"]["tok"]
    x = embed(tok, tokens, cfg)

    @jax.checkpoint
    def body(x, w):
        return layer_forward(w, x, positions, cfg, precision), None

    x, _ = jax.lax.scan(body, x, _layers_view(params))
    logits = head(x, params["final_norm"]["scale"], tok, cfg, precision)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    return jnp.mean(logz - gold)


loss_and_grad = jax.jit(jax.value_and_grad(loss), static_argnums=(3, 4))


def lr_at(step: int, opt: dict) -> float:
    """Warm-up then cosine to a tenth, as the configuration states."""
    import math

    base, warm, total = opt["lr"], opt["warmup_steps"], opt["total_steps"]
    if step < warm:
        return base * step / max(warm, 1)
    prog = min(max((step - warm) / max(total - warm, 1), 0.0), 1.0)
    return base * (0.1 + 0.9 * 0.5 * (1 + math.cos(math.pi * prog)))


@partial(jax.jit, donate_argnums=(0, 2, 3))
def _adam_leaf(w, g, m, v, lr, scale, t, hp):
    b1, b2, eps, wd = hp[0], hp[1], hp[2], hp[3]
    g = g * scale
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * g * g
    mh = m / (1 - b1 ** t)
    vh = v / (1 - b2 ** t)
    return w - lr * (mh / (jnp.sqrt(vh) + eps) + wd * w), m, v


def adamw(params, grads, state, step: int, opt: dict):
    """One AdamW step of the configuration's optimizer, leaf by leaf, with
    the gradient clipped to a global norm. Decay applies to leaves of
    rank two or more as they are stored (layers stacked). The moments are
    kept in host memory between steps and each leaf's go to the device
    only for its update, so that the float32 weights, their gradients and
    the activations of the next step have the chip to themselves. Returns
    the new params and state, and the clip factor applied."""
    flat_g = jax.tree.leaves(grads)
    gnorm = float(jnp.sqrt(sum(jnp.sum(g * g) for g in flat_g)))
    clip = min(1.0, opt["grad_clip"] / max(gnorm, 1e-8))
    flat_w, tdef = jax.tree.flatten(params)
    if state is None:
        state = tdef.unflatten([(np.zeros(w.shape, np.float32),
                                 np.zeros(w.shape, np.float32))
                                for w in flat_w])
    flat_s = tdef.flatten_up_to(state)
    lr = lr_at(step, opt)
    new_w, new_s = [], []
    for w, g, (m, v) in zip(flat_w, flat_g, flat_s):
        wd = opt["weight_decay"] if w.ndim >= 2 else 0.0
        hp = jnp.asarray([opt["b1"], opt["b2"], opt["eps"], wd], F32)
        w2, m2, v2 = _adam_leaf(w, g, jnp.asarray(m), jnp.asarray(v),
                                jnp.float32(lr), jnp.float32(clip),
                                jnp.float32(step), hp)
        new_w.append(w2)
        new_s.append((np.asarray(m2), np.asarray(v2)))
        del m2, v2
    return tdef.unflatten(new_w), tdef.unflatten(new_s), clip
