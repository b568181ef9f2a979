"""Plain reference of RWKV-6 "Finch", the rwkv6 configuration's model.

Written from Peng et al., "Eagle and Finch" (arXiv:2404.05892, §4) and
RWKV-LM's ``x060`` time and channel mix. It imports nothing of the
program: no chunked algebra, no kernel, no code from ``models/``. It
computes in float32 with every matmul at the highest precision (under
``jax.default_matmul_precision("highest")`` as well), and the wkv as the
per-token recurrence

    S_t = diag(w_t) S_{t-1} + k_t^T v_t,   o_t = r_t (S_{t-1} + diag(u) k_t^T v_t)

in a ``lax.scan``, checkpointed in blocks of tokens so that its gradient
keeps one state a block. Each layer is recomputed in the backward pass.

Departures, each also the program's: the embedding and the head are
padded to a multiple of 256 rows, and the padded rows are not part of the
model (logits over the published vocabulary only); ``rescale_every`` of
the configuration (a float16 inference rescaling that leaves the
arithmetic unchanged) is not applied. The decay is not clipped.

``precision="fp8"`` is the control, as :mod:`reference`'s: every matmul
input and the wkv's r, k and v are rounded to float8 (e4m3) in the
forward pass, the step below the configuration's bfloat16.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

import reference as R
import weights_rwkv6 as W6

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST


def _ln(x, gain, bias, eps):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * gain + bias


def _previous(x):
    """Each token's predecessor along the sequence, zero before the first."""
    return jnp.concatenate([jnp.zeros_like(x[:, :1]), x[:, :-1]], axis=1)


def wkv(r, k, v, w, u, block: int = 64):
    """The recurrence over (B, T, H, K) inputs, w the decay factors, u (H,
    K) the current token's bonus; o (B, T, H, V). Tokens go through a
    scan one at a time, in blocks each recomputed in the backward pass."""
    B, T, H, K = r.shape
    pad = (-T) % block
    if pad:     # padded tokens come after every real one: r, k, v zero
        z = ((0, 0), (0, pad), (0, 0), (0, 0))
        r, k, v = (jnp.pad(a, z) for a in (r, k, v))
        w = jnp.pad(w, z, constant_values=1.0)

    def token(S, xs):
        r_t, k_t, v_t, w_t = xs                         # (B, H, K)
        kv = k_t[..., :, None] * v_t[..., None, :]      # (B, H, K, V)
        o = jnp.einsum("bhk,bhkv->bhv", r_t, S + u[..., None] * kv,
                       precision=HIGHEST)
        return w_t[..., None] * S + kv, o

    @jax.checkpoint
    def one_block(S, xs):
        return jax.lax.scan(token, S, xs)

    n = (T + pad) // block
    xs = tuple(a.reshape(B, n, block, H, K).transpose(1, 2, 0, 3, 4)
               for a in (r, k, v, w))
    S0 = jnp.zeros((B, H, K, v.shape[-1]), F32)
    _, o = jax.lax.scan(one_block, S0, xs)              # (n, block, B, H, V)
    return o.transpose(2, 0, 1, 3, 4).reshape(B, n * block, H, -1)[:, :T]


def time_mix(w: dict, x, cfg: dict, precision: str = "f32"):
    """Finch's time mix of x (B, T, d), LN1 of the residual stream."""
    m = W6.dims(cfg)
    B, T, d = x.shape
    H, hs, Rk = m["H"], m["hs"], m["R"]
    mm = lambda a, b: R._mm(a, b, precision)    # noqa: E731
    delta = _previous(x) - x
    xxx = x + delta * w["mix_x"]
    lo = jnp.tanh(mm(xxx, w["mix_a"])).reshape(B, T, len(W6.MIXES), Rk)
    lo = jnp.einsum("btfr,frd->fbtd", R._q(lo, precision),
                    R._q(w["mix_b"], precision), precision=HIGHEST)
    xw, xk, xv, xr, xg = (x + delta * (w["mix"][i] + lo[i])
                          for i in range(len(W6.MIXES)))
    r = mm(xr, w["wr"]).reshape(B, T, H, hs)
    k = mm(xk, w["wk"]).reshape(B, T, H, hs)
    v = mm(xv, w["wv"]).reshape(B, T, H, hs)
    g = jax.nn.silu(mm(xg, w["wg"]))
    decay = jnp.exp(-jnp.exp(w["w0"] + mm(jnp.tanh(mm(xw, w["wa"])),
                                          w["wb"])))
    o = wkv(R._q(r, precision), R._q(k, precision), R._q(v, precision),
            decay.reshape(B, T, H, hs), w["u"])
    eps = 1e-5 * cfg["head_size_divisor"] ** 2
    o = _ln(o, w["ln_scale"], w["ln_bias"], eps)        # per head: GroupNorm
    return mm(o.reshape(B, T, d) * g, w["wo"])


def channel_mix(w: dict, x, precision: str = "f32"):
    """Finch's channel mix of x (B, T, d), LN2 of the residual stream."""
    mm = lambda a, b: R._mm(a, b, precision)    # noqa: E731
    delta = _previous(x) - x
    xk = x + delta * w["mix"][0]
    xr = x + delta * w["mix"][1]
    kk = jnp.square(jax.nn.relu(mm(xk, w["wk"])))
    return jax.nn.sigmoid(mm(xr, w["wr"])) * mm(kk, w["wv"])


def layer_forward(w: dict, x, cfg: dict, precision: str = "f32"):
    """One block: x += TimeMix(LN1 x); x += ChannelMix(LN2 x)."""
    eps = cfg["layer_norm_epsilon"]
    x = x + time_mix(w["time_mix"],
                     _ln(x, w["norm1"]["scale"], w["norm1"]["bias"], eps),
                     cfg, precision)
    return x + channel_mix(w["channel_mix"],
                           _ln(x, w["norm2"]["scale"], w["norm2"]["bias"],
                               eps), precision)


def logits(params, tokens, cfg: dict, precision: str = "f32"):
    """Logits (B, T, V) over the published vocabulary: the embedding, LN0,
    the blocks, LN_out and the untied head."""
    with jax.default_matmul_precision("highest"):
        eps = cfg["layer_norm_epsilon"]
        x = params["embed"]["tok"][tokens].astype(F32)
        x = _ln(x, params["ln0"]["scale"], params["ln0"]["bias"], eps)

        @jax.checkpoint
        def body(x, w):
            return layer_forward(w, x, cfg, precision), None

        x, _ = jax.lax.scan(body, x, params["layers"])
        x = _ln(x, params["final_norm"]["scale"], params["final_norm"]["bias"],
                eps)
        return R._mm(x, params["embed"]["head"][:, :cfg["vocab_size"]],
                     precision)


def loss(params, tokens, labels, cfg: dict, precision: str = "f32"):
    """Mean next-token cross-entropy over every position of the batch."""
    z = logits(params, tokens, cfg, precision)
    gold = jnp.take_along_axis(z, labels[..., None], -1)[..., 0]
    return jnp.mean(jax.nn.logsumexp(z, axis=-1) - gold)


loss_and_grad = jax.jit(jax.value_and_grad(loss), static_argnums=(3, 4))
logits_jit = jax.jit(logits, static_argnums=(2, 3))


def float32_params(cfg: dict, seed: int):
    """The seed's weights as the program is given them, in float32."""
    return jax.jit(lambda p: jax.tree.map(lambda x: x.astype(F32), p))(
        W6.make_params(cfg, seed, cfg["dtype"]))
