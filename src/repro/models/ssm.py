"""Linear-attention / SSM layers: RWKV6 (Finch) and Mamba-2-style SSD.

Both are instances of one primitive — a decayed linear-attention recurrence

    S_t = diag(exp(ld_t)) . S_{t-1} + k_t (x) v_t          (state: K x V)
    o_t = q_t @ S_{t-1} + (q_t . u . k_t) v_t              (rwkv6: bonus u)
    o_t = q_t @ S_t                                        (ssd)

implemented three ways:
* ``recurrent_scan``   — exact per-token scan (oracle + long-context decode)
* ``chunked``          — chunk-parallel form: cross-chunk state recurrence +
                         intra-chunk pairwise-decay attention. All decay
                         factors are exp(<=0) so it is numerically safe at
                         any sequence length. This is the training/prefill
                         path and the CPU-lowerable stand-in for the Pallas
                         wkv kernel.
* ``pallas``           — repro.kernels.rwkv6 (TPU target).

Shapes: q,k,ld: (B,T,H,K); v: (B,T,H,V); state: (B,H,K,V).
For SSD the decay is scalar per head (K=state_size holds k; ld broadcasts).
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.runtime import trace_names as N


# ------------------------------------------------------------- primitive
def linear_attention_step(state, q, k, v, ld, u=None):
    """One recurrence step. q,k,ld:(B,H,K) v:(B,H,V) state:(B,H,K,V)."""
    kv = k[..., :, None] * v[..., None, :]                 # (B,H,K,V)
    if u is None:  # ssd: include current token after decay
        state = jnp.exp(ld)[..., None] * state + kv
        o = jnp.einsum("bhk,bhkv->bhv", q, state)
    else:          # rwkv6: bonus weight on current token
        o = jnp.einsum("bhk,bhkv->bhv", q, state) + jnp.einsum(
            "bhk,bhkv->bhv", q * u, kv)
        state = jnp.exp(ld)[..., None] * state + kv
    return state, o


def recurrent_linear_attention(q, k, v, ld, u=None, initial_state=None):
    B, T, H, K = q.shape
    V = v.shape[-1]
    state0 = (initial_state if initial_state is not None
              else jnp.zeros((B, H, K, V), jnp.float32))

    def step(s, xs):
        qi, ki, vi, ldi = xs
        s, o = linear_attention_step(s, qi, ki, vi, ldi, u)
        return s, o

    xs = tuple(jnp.moveaxis(a.astype(jnp.float32), 1, 0) for a in (q, k, v, ld))
    state, o = jax.lax.scan(step, state0, xs)
    return jnp.moveaxis(o, 0, 1), state


def chunked_linear_attention(q, k, v, ld, u=None, initial_state=None,
                             chunk: int = 64, factored: bool = False,
                             sub: int = 16):
    """Chunk-parallel decayed linear attention (see module docstring).

    All heavy per-chunk work (f32 upcast, pairwise-decay intra-chunk
    attention, state recurrence) happens inside a scan over chunks, so peak
    memory is O(B*c^2*H*K) regardless of T and the inputs stay in their
    compute dtype (bf16) outside the loop. The chunk body is checkpointed,
    so the same holds of the backward pass.

    factored=True (§Perf): two-level intra-chunk scheme. Cross-sub-chunk
    terms factor around the sub-chunk boundary b (s < b <= t):
        A[t,s] = (q_t exp(w_t - p_b)) . (k_s exp(p_b - p_s))
    — both factors exp(<=0), so they are plain safe matmuls; only the
    (sub x sub) diagonal blocks need the pairwise (r,r,K) tensor. This
    removes the O(c^2 K) exp tensor (the memory-term hot spot on rwkv6)
    at identical math.
    """
    B, T, H, K = q.shape
    V = v.shape[-1]
    dtype = q.dtype
    c = min(chunk, T)
    pad = (-T) % c
    if pad:
        z3 = ((0, 0), (0, pad), (0, 0), (0, 0))
        q, k, v = (jnp.pad(a, z3) for a in (q, k, v))
        ld = jnp.pad(ld, z3)  # ld=0 on padding -> decay 1, state unchanged;
        # padded k rows are zero so they add nothing to the state.
    n = q.shape[1] // c
    f32 = jnp.float32
    qc = jnp.moveaxis(q.reshape(B, n, c, H, K), 1, 0)
    kc = jnp.moveaxis(k.reshape(B, n, c, H, K), 1, 0)
    vc = jnp.moveaxis(v.reshape(B, n, c, H, V), 1, 0)
    ldc = jnp.moveaxis(ld.reshape(B, n, c, H, K), 1, 0)

    tgrid = jnp.arange(c)
    mask = (tgrid[:, None] >= tgrid[None, :]) if u is None else (
        tgrid[:, None] > tgrid[None, :])

    state0 = (initial_state.astype(f32) if initial_state is not None
              else jnp.zeros((B, H, K, V), f32))

    r = min(sub, c)
    nsub = c // r if c % r == 0 else 0
    sgrid = jnp.arange(r)
    smask = (sgrid[:, None] >= sgrid[None, :]) if u is None else (
        sgrid[:, None] > sgrid[None, :])

    def _intra_pairwise(qi, ki, vi, w_exp, p_inc):
        diff = w_exp[:, :, None] - p_inc[:, None, :]       # (B,c,c,H,K)
        diff = jnp.where(mask[None, :, :, None, None], diff, -jnp.inf)
        A = jnp.einsum("bthk,bshk,btshk->bhts", qi, ki, jnp.exp(diff))
        return jnp.einsum("bhts,bshv->bthv", A, vi)

    def _intra_factored(qi, ki, vi, w_exp, p_inc):
        """Two-level scheme: sub-chunk state recurrence (exact, over nsub
        steps) + (r,r,K) pairwise diagonals only."""
        Bl, _, Hl, Kl = qi.shape
        Vl = vi.shape[-1]
        qs = qi.reshape(Bl, nsub, r, Hl, Kl)
        ks = ki.reshape(Bl, nsub, r, Hl, Kl)
        vs = vi.reshape(Bl, nsub, r, Hl, Vl)
        we = w_exp.reshape(Bl, nsub, r, Hl, Kl)
        pi = p_inc.reshape(Bl, nsub, r, Hl, Kl)
        # diagonal blocks: pairwise over r only
        dd = we[:, :, :, None] - pi[:, :, None, :]          # (B,n,r,r,H,K)
        dd = jnp.where(smask[None, None, :, :, None, None], dd, -jnp.inf)
        Ad = jnp.einsum("bnthk,bnshk,bntshk->bnhts", qs, ks, jnp.exp(dd))
        o = jnp.einsum("bnhts,bnshv->bnthv", Ad, vs)
        # cross-sub-chunk via an inner state recurrence (factored matmuls)
        p_end = pi[:, :, -1]                                # (B,n,H,K)
        p_end_prev = jnp.concatenate(
            [jnp.zeros_like(p_end[:, :1]), p_end[:, :-1]], 1)
        pe_delta = p_end - p_end_prev                       # within-sub decay
        k_dec = ks * jnp.exp(p_end[:, :, None] - pi)        # exp(<=0)
        kv_sub = jnp.einsum("bnrhk,bnrhv->bnhkv", k_dec, vs)
        q_dec = qs * jnp.exp(we - p_end_prev[:, :, None])
        # prefix state over sub-chunks (within this chunk, S0 = 0); the
        # state is always referenced to the END of the previous sub-chunk.
        def sub_step(Ssub, xs_):
            qd, pd, kvs = xs_
            o_s = jnp.einsum("brhk,bhkv->brhv", qd, Ssub)
            Ssub = jnp.exp(pd)[..., None] * Ssub + kvs
            return Ssub, o_s
        xs_ = (jnp.moveaxis(q_dec, 1, 0), jnp.moveaxis(pe_delta, 1, 0),
               jnp.moveaxis(kv_sub, 1, 0))
        _, o_cross = jax.lax.scan(
            sub_step, jnp.zeros((Bl, Hl, Kl, Vl), f32), xs_)
        o = o + jnp.moveaxis(o_cross, 0, 1)
        return o.reshape(Bl, c, Hl, Vl)

    intra = _intra_factored if (factored and nsub) else _intra_pairwise

    def chunk_step(S, xs):
        qi, ki, vi, ldi = (a.astype(f32) for a in xs)      # (B,c,H,*)
        p_inc = jnp.cumsum(ldi, axis=1)
        p_exc = p_inc - ldi
        w_exp = p_inc if u is None else p_exc
        o = intra(qi, ki, vi, w_exp, p_inc)
        if u is not None:                                   # bonus diagonal
            diag = jnp.einsum("bthk,hk,bthk->bth", qi, u.astype(f32), ki)
            o = o + diag[..., None] * vi
        # cross-chunk state contribution
        o = o + jnp.einsum("bthk,bhkv->bthv", qi * jnp.exp(w_exp), S)
        # state recurrence to chunk end
        p_last = p_inc[:, -1]                               # (B,H,K)
        k_dec = ki * jnp.exp(p_last[:, None] - p_inc)
        S = jnp.exp(p_last)[..., None] * S + jnp.einsum(
            "bthk,bthv->bhkv", k_dec, vi)
        return S, o.astype(dtype)

    # each chunk is recomputed in the backward pass: the scan then keeps one
    # state a chunk, not every chunk's intermediates (at rwkv6-3b's 40
    # heads x 4096 tokens those are several GB of padded float32)
    state, o = jax.lax.scan(jax.checkpoint(chunk_step), state0,
                            (qc, kc, vc, ldc))
    o = jnp.moveaxis(o, 0, 1).reshape(B, n * c, H, V)[:, :T]
    return o, state


def linear_attention(q, k, v, ld, u=None, initial_state=None, *,
                     backend: str = "chunked", chunk: int = None,
                     factored: bool = False):
    """chunk None = auto: the pallas backend resolves its tile from the
    tuned-config cache (repro.kernels.tuning); chunked falls back to 64."""
    if backend == "recurrent":
        return recurrent_linear_attention(q, k, v, ld, u, initial_state)
    if backend == "chunked":
        return chunked_linear_attention(q, k, v, ld, u, initial_state,
                                        chunk=chunk if chunk else 64,
                                        factored=factored)
    if backend == "pallas":
        from repro.kernels import ops as kops
        return kops.wkv6(q, k, v, ld, u, initial_state, chunk=chunk)
    raise ValueError(backend)


# ------------------------------------------------------------- RWKV6 layer
# RWKV-6 "Finch" (arXiv:2404.05892 §4; RWKV-LM's ``x060`` time mix). The
# five token-shift mixes, in the order of the modelling code's adapter.
MIXES = ("w", "k", "v", "r", "g")


def rwkv6_init(key, cfg: ModelConfig, dtype) -> dict:
    """One layer's time-mix weights. The per-channel schedules (token-shift
    mixes ``mix_x``/``mix``, base log-log decay ``w0``) are Finch's at
    layer 0; the adapters' second factors start small."""
    d = cfg.d_model
    sc = cfg.ssm
    hs = sc.head_size
    H = d // hs
    ks = jax.random.split(key, 10)
    s = 1.0 / np.sqrt(d)
    ramp = np.arange(d) / d
    shift = {"w": 1 - ramp, "k": 1 - ramp, "v": 1 - ramp,
             "r": 1 - ramp ** 0.5, "g": 1 - ramp ** 0.5}
    return {
        "mix_x": jnp.asarray(1 - ramp, jnp.float32),
        "mix": jnp.asarray(np.stack([shift[m] for m in MIXES]), jnp.float32),
        "mix_a": (jax.random.normal(ks[8], (d, len(MIXES) * sc.mix_rank))
                  * s).astype(dtype),
        "mix_b": (jax.random.normal(ks[9], (len(MIXES), sc.mix_rank, d))
                  * 0.01).astype(dtype),
        "wr": (jax.random.normal(ks[0], (d, d)) * s).astype(dtype),
        "wk": (jax.random.normal(ks[1], (d, d)) * s).astype(dtype),
        "wv": (jax.random.normal(ks[2], (d, d)) * s).astype(dtype),
        "wg": (jax.random.normal(ks[3], (d, d)) * s).astype(dtype),
        "wo": (jax.random.normal(ks[4], (d, d)) * s).astype(dtype),
        "w0": jnp.asarray(-6 + 5 * np.linspace(0, 1, d) ** 0.7, jnp.float32),
        "wa": (jax.random.normal(ks[5], (d, sc.decay_rank)) * s).astype(dtype),
        "wb": (jax.random.normal(ks[6], (sc.decay_rank, d)) * 0.01
               ).astype(dtype),
        "u": (jax.random.normal(ks[7], (H, hs)) * 0.1).astype(jnp.float32),
        "ln_scale": jnp.ones((H, hs), jnp.float32),     # per-head groupnorm
        "ln_bias": jnp.zeros((H, hs), jnp.float32),
    }


def _token_shift(x, prev):
    """x one token later: prev (B, d), the token before x's first (zeros
    at a row's start), then x[:, :-1]."""
    return jnp.concatenate([prev[:, None], x[:, :-1]], axis=1)


def rwkv6_mix_inputs(p, x, xprev, cfg: ModelConfig):
    """Finch's data-dependent token shift ("ddlerp"), the projections and
    the data-dependent decay. x, xprev: (B, T, d), xprev the previous
    token's x. Returns r, k, v (B, T, H, hs) and g (B, T, d) in x's dtype,
    and the log decay ld = -exp(w0 + tanh(x_w A_d) B_d) (B, T, H, hs) in
    float32."""
    B, T, d = x.shape
    hs, R = cfg.ssm.head_size, cfg.ssm.mix_rank
    delta = xprev - x
    xxx = x + delta * p["mix_x"].astype(x.dtype)
    m = jnp.tanh(xxx @ p["mix_a"]).reshape(B, T, len(MIXES), R)
    m = jnp.einsum("btfr,frd->fbtd", m, p["mix_b"])
    mix = p["mix"].astype(x.dtype)
    xw, xk, xv, xr, xg = (x + delta * (mix[i] + m[i])
                          for i in range(len(MIXES)))
    r = (xr @ p["wr"]).reshape(B, T, d // hs, hs)
    k = (xk @ p["wk"]).reshape(B, T, d // hs, hs)
    v = (xv @ p["wv"]).reshape(B, T, d // hs, hs)
    g = jax.nn.silu(xg @ p["wg"])
    dd = p["w0"] + (jnp.tanh(xw @ p["wa"]) @ p["wb"]).astype(jnp.float32)
    return r, k, v, g, -jnp.exp(dd).reshape(B, T, d // hs, hs)


def rwkv6_output(p, o, g, cfg: ModelConfig):
    """The wkv's output o (B, T, H, hs) through the per-head GroupNorm (in
    float32), the gate g and the output projection."""
    B, T = o.shape[:2]
    of = o.astype(jnp.float32)
    mean = of.mean(-1, keepdims=True)
    var = ((of - mean) ** 2).mean(-1, keepdims=True)
    of = ((of - mean) * jax.lax.rsqrt(var + cfg.ssm.group_norm_eps)
          * p["ln_scale"] + p["ln_bias"])
    return (of.reshape(B, T, -1).astype(g.dtype) * g) @ p["wo"]


def rwkv6_time_mix(p, x, cfg: ModelConfig, *, backend: str,
                   state=None, shift_prev=None, factored: bool = False,
                   chunk: int = None):
    """x: (B,T,d), LN1 of the residual stream. Returns (out, (wkv_state,
    last_token)). A decode step is T = 1 with the recurrent backend and the
    carried state and last token.

    chunk None = auto: pallas resolves the tuned tile, other backends use
    cfg.ssm.chunk_size; an explicit value overrides both."""
    B, T, d = x.shape
    prev = shift_prev if shift_prev is not None else jnp.zeros((B, d), x.dtype)
    with jax.named_scope(N.TIME_MIX):
        r, k, v, g, ld = rwkv6_mix_inputs(p, x, _token_shift(x, prev), cfg)
        if chunk is None and backend != "pallas":
            chunk = cfg.ssm.chunk_size
        with jax.named_scope(N.WKV):
            o, new_state = linear_attention(r, k, v, ld, u=p["u"],
                                            initial_state=state,
                                            backend=backend, chunk=chunk,
                                            factored=factored)
        out = rwkv6_output(p, o, g, cfg)
    return out, (new_state, x[:, -1])


def rwkv6_channel_mix_init(key, cfg: ModelConfig, dtype) -> dict:
    d, f = cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 3)
    ramp = np.arange(d) / d
    return {
        "mix": jnp.asarray(np.stack([1 - ramp, 1 - ramp]), jnp.float32),
        "wk": (jax.random.normal(ks[0], (d, f)) / np.sqrt(d)).astype(dtype),
        "wv": (jax.random.normal(ks[1], (f, d)) / np.sqrt(f)).astype(dtype),
        "wr": (jax.random.normal(ks[2], (d, d)) / np.sqrt(d)).astype(dtype),
    }


def rwkv6_channel_mix(p, x, *, shift_prev=None):
    """x: (B,T,d), LN2 of the residual stream: a squared-ReLU key, gated
    by a sigmoid receptance, each on its own static token shift."""
    B, T, d = x.shape
    prev = shift_prev if shift_prev is not None else jnp.zeros((B, d), x.dtype)
    with jax.named_scope(N.CHANNEL_MIX):
        delta = _token_shift(x, prev) - x
        mix = p["mix"].astype(x.dtype)
        xk = x + delta * mix[0]
        xr = x + delta * mix[1]
        kk = jnp.square(jax.nn.relu(xk @ p["wk"]))
        return jax.nn.sigmoid(xr @ p["wr"]) * (kk @ p["wv"]), x[:, -1]


# ------------------------------------------------------------- SSD (hymba)
def ssd_init(key, cfg: ModelConfig, dtype) -> dict:
    d = cfg.ssm.head_size * max(1, cfg.d_model // cfg.ssm.head_size)
    dm = cfg.d_model
    H = d // cfg.ssm.head_size
    N = cfg.ssm.state_size
    ks = jax.random.split(key, 6)
    s = 1.0 / np.sqrt(dm)
    return {
        "wx": (jax.random.normal(ks[0], (dm, d)) * s).astype(dtype),
        "wz": (jax.random.normal(ks[1], (dm, d)) * s).astype(dtype),
        "wB": (jax.random.normal(ks[2], (dm, H * N)) * s).astype(dtype),
        "wC": (jax.random.normal(ks[3], (dm, H * N)) * s).astype(dtype),
        "wdt": (jax.random.normal(ks[4], (dm, H)) * s).astype(dtype),
        "dt_bias": jnp.zeros((H,), jnp.float32),
        "A_log": jnp.zeros((H,), jnp.float32),
        "D": jnp.ones((H,), jnp.float32),
        "wo": (jax.random.normal(ks[5], (d, dm)) / np.sqrt(d)).astype(dtype),
    }


def ssd_mix(p, x, cfg: ModelConfig, *, backend: str, state=None,
            factored: bool = False, chunk: int = None):
    """Mamba-2-style SSD head mix. x:(B,T,dm) -> (out, state).
    chunk: see rwkv6_time_mix."""
    B, T, dm = x.shape
    hs = cfg.ssm.head_size
    N = cfg.ssm.state_size
    H = p["wx"].shape[1] // hs
    xin = (x @ p["wx"]).reshape(B, T, H, hs)
    z = jax.nn.silu(x @ p["wz"])
    Bm = (x @ p["wB"]).reshape(B, T, H, N)
    Cm = (x @ p["wC"]).reshape(B, T, H, N)
    dt = jax.nn.softplus((x @ p["wdt"]).astype(jnp.float32) + p["dt_bias"])
    ld = (-dt * jnp.exp(p["A_log"]))[..., None]           # (B,T,H,1) scalar/head
    ld = jnp.broadcast_to(jnp.clip(ld, -12.0, -1e-6), (B, T, H, N))
    k = Bm * dt[..., None].astype(Bm.dtype)               # discretized input
    if chunk is None and backend != "pallas":
        chunk = cfg.ssm.chunk_size
    o, new_state = linear_attention(Cm, k, xin, ld, u=None,
                                    initial_state=state, backend=backend,
                                    chunk=chunk, factored=factored)
    o = o + p["D"][:, None] * xin.astype(jnp.float32)
    out = (o.reshape(B, T, H * hs).astype(x.dtype) * z) @ p["wo"]
    return out, new_state
