"""Shared building blocks: norms, rotary embeddings, MLPs, embeddings.

Everything is functional: ``*_init(key, cfg) -> params`` and a matching
apply function. Params are plain nested dicts so they can be stacked along
a leading layer axis and scanned.
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.runtime import trace_names as N

# M-RoPE head-dim half split into (temporal, height, width) sections,
# per Qwen2-VL (arXiv:2409.12191).
MROPE_SECTIONS = (16, 24, 24)


def _dtype(cfg_dtype: str):
    return jnp.dtype(cfg_dtype)


# ---------------------------------------------------------------- norms
def norm_init(d: int, kind: str):
    p = {"scale": jnp.ones((d,), jnp.float32)}
    if kind == "layernorm":
        p["bias"] = jnp.zeros((d,), jnp.float32)
    return p


def apply_norm(p, x, kind: str, eps: float = 1e-5):
    """Norm in f32, output in input dtype."""
    with jax.named_scope(N.NORM):
        xf = x.astype(jnp.float32)
        if kind == "layernorm":
            mean = xf.mean(-1, keepdims=True)
            var = ((xf - mean) ** 2).mean(-1, keepdims=True)
            y = (xf - mean) * jax.lax.rsqrt(var + eps)
            y = y * p["scale"] + p["bias"]
        else:  # rmsnorm
            ms = (xf * xf).mean(-1, keepdims=True)
            y = xf * jax.lax.rsqrt(ms + eps) * p["scale"]
        return y.astype(x.dtype)


# ---------------------------------------------------------------- rotary
def rope_freqs(head_dim: int, theta: float) -> jnp.ndarray:
    half = head_dim // 2
    return 1.0 / (theta ** (np.arange(0, half, dtype=np.float32) / half))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x: (..., S, H, D); positions: broadcastable to (..., S)."""
    half = x.shape[-1] // 2
    freqs = rope_freqs(x.shape[-1], theta)                    # (half,)
    ang = positions[..., None].astype(jnp.float32) * freqs    # (..., S, half)
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def apply_mrope(x: jnp.ndarray, positions3: jnp.ndarray, theta: float) -> jnp.ndarray:
    """Multimodal RoPE (Qwen2-VL): rotary angle sections come from three
    position streams (t, h, w).

    x: (B, S, H, D); positions3: (B, 3, S).
    """
    half = x.shape[-1] // 2
    if sum(MROPE_SECTIONS) == half:
        sections = MROPE_SECTIONS
    else:  # reduced configs: keep the (1/4, 3/8, 3/8) proportions
        s0 = half // 4
        s1 = (half - s0) // 2
        sections = (s0, s1, half - s0 - s1)
    freqs = rope_freqs(x.shape[-1], theta)                    # (half,)
    # angles per stream: (B, 3, S, half)
    ang_all = positions3[..., None].astype(jnp.float32) * freqs
    parts = []
    start = 0
    for i, sec in enumerate(sections):
        parts.append(ang_all[:, i, :, start:start + sec])
        start += sec
    ang = jnp.concatenate(parts, axis=-1)                     # (B, S, half)
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_positions(seq: int, d: int) -> jnp.ndarray:
    pos = np.arange(seq)[:, None]
    dim = np.arange(0, d, 2)[None, :]
    ang = pos / np.power(10000.0, dim / d)
    out = np.zeros((seq, d), np.float32)
    out[:, 0::2] = np.sin(ang)
    out[:, 1::2] = np.cos(ang)
    return jnp.asarray(out)


# ---------------------------------------------------------------- mlp
def mlp_init(key, d: int, f: int, activation: str, dtype) -> dict:
    k1, k2, k3 = jax.random.split(key, 3)
    scale_in = 1.0 / np.sqrt(d)
    scale_out = 1.0 / np.sqrt(f)
    p = {
        "w_in": (jax.random.normal(k1, (d, f)) * scale_in).astype(dtype),
        "w_out": (jax.random.normal(k2, (f, d)) * scale_out).astype(dtype),
    }
    if activation == "swiglu":
        p["w_gate"] = (jax.random.normal(k3, (d, f)) * scale_in).astype(dtype)
    return p


def apply_mlp(p, x, activation: str):
    if activation == "swiglu":
        h = jax.nn.silu(x @ p["w_gate"]) * (x @ p["w_in"])
    elif activation == "gelu":
        h = jax.nn.gelu(x @ p["w_in"], approximate=True)
    elif activation == "rwkv":  # squared-relu channel-mix (no gate matrix here)
        h = jnp.square(jax.nn.relu(x @ p["w_in"]))
    else:
        raise ValueError(activation)
    return h @ p["w_out"]


# ---------------------------------------------------------------- embedding
VOCAB_PAD = 256  # pad vocab so it always divides the model axis (MaxText-style)


def padded_vocab(vocab_size: int) -> int:
    return -(-vocab_size // VOCAB_PAD) * VOCAB_PAD


def embedding_init(key, cfg: ModelConfig, dtype) -> dict:
    k1, k2 = jax.random.split(key)
    pv = padded_vocab(cfg.vocab_size)
    p = {"tok": (jax.random.normal(k1, (pv, cfg.d_model)) * 0.02)
         .astype(dtype)}
    if not cfg.tie_embeddings:
        p["head"] = (jax.random.normal(k2, (cfg.d_model, pv))
                     * 0.02).astype(dtype)
    return p


def embed_tokens(p, tokens):
    return jnp.take(p["tok"], tokens, axis=0)


def lm_logits(p, x, tie: bool, true_vocab: int = 0):
    """Logits over the PADDED vocab; padded entries masked to -inf when
    true_vocab is given."""
    logits = x @ p["tok"].T if tie else x @ p["head"]
    if true_vocab and logits.shape[-1] != true_vocab:
        mask = jnp.arange(logits.shape[-1]) < true_vocab
        logits = jnp.where(mask, logits, jnp.asarray(-1e30, logits.dtype))
    return logits
