"""Pallas kernel validation: interpret-mode execution vs pure-jnp oracles,
with hypothesis sweeps over shapes/dtypes."""
import numpy as np
from hypothesis import given, settings, strategies as st

import jax
import jax.numpy as jnp

from repro.kernels import ops, ref

settings.register_profile("kernels", max_examples=10, deadline=None)
settings.load_profile("kernels")


def _rand(rng, shape, dtype):
    x = rng.standard_normal(shape).astype(np.float32)
    return jnp.asarray(x, dtype)


def _tol(dtype):
    return 2e-2 if dtype == jnp.bfloat16 else 5e-4


# ------------------------------------------------------------------ flash
@given(
    b=st.sampled_from([1, 2]),
    s=st.sampled_from([128, 256, 384]),
    heads=st.sampled_from([(2, 1), (4, 2), (4, 4), (8, 2)]),
    d=st.sampled_from([64, 128]),
    causal=st.booleans(),
    window=st.sampled_from([0, 96]),
    dtype=st.sampled_from([jnp.float32, jnp.bfloat16]),
)
def test_flash_attention_matches_oracle(b, s, heads, d, causal, window,
                                        dtype):
    hq, hkv = heads
    if window and not causal:
        window = 0
    rng = np.random.default_rng(b * 1000 + s + hq)
    q = _rand(rng, (b, s, hq, d), dtype)
    k = _rand(rng, (b, s, hkv, d), dtype)
    v = _rand(rng, (b, s, hkv, d), dtype)
    out = ops.flash_attention(q, k, v, causal=causal, window=window)
    want = ref.flash_attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32),
        atol=_tol(dtype), rtol=_tol(dtype))


def test_flash_attention_block_shape_sweep():
    rng = np.random.default_rng(0)
    q = _rand(rng, (1, 512, 4, 64), jnp.float32)
    k = _rand(rng, (1, 512, 2, 64), jnp.float32)
    v = _rand(rng, (1, 512, 2, 64), jnp.float32)
    want = ref.flash_attention_ref(q, k, v, causal=True)
    for bq, bk in [(128, 128), (256, 128), (128, 256), (512, 512)]:
        out = ops.flash_attention(q, k, v, causal=True,
                                  block_q=bq, block_k=bk)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   atol=5e-5, rtol=5e-5)


# ------------------------------------------------------------------ wkv6
@given(
    mode=st.sampled_from(["rwkv", "ssd"]),
    t=st.sampled_from([64, 96, 128]),
    h=st.sampled_from([1, 3]),
    kdim=st.sampled_from([16, 64]),
    chunk=st.sampled_from([16, 32, 64]),
    dtype=st.sampled_from([jnp.float32, jnp.bfloat16]),
)
def test_wkv6_matches_recurrence(mode, t, h, kdim, chunk, dtype):
    if t % chunk:
        chunk = 16
    rng = np.random.default_rng(t + h * 7 + kdim)
    B, V = 2, kdim
    q = _rand(rng, (B, t, h, kdim), dtype)
    k = _rand(rng, (B, t, h, kdim), dtype)
    v = _rand(rng, (B, t, h, V), dtype)
    ld = jnp.asarray(-np.exp(rng.standard_normal((B, t, h, kdim)) - 1.0),
                     jnp.float32)
    u = (jnp.asarray(rng.standard_normal((h, kdim)), jnp.float32)
         if mode == "rwkv" else None)
    o, s = ops.wkv6(q, k, v, ld, u, chunk=chunk)
    ow, sw = ref.wkv6_ref(q.astype(jnp.float32), k.astype(jnp.float32),
                          v.astype(jnp.float32), ld, u)
    tol = 5e-2 if dtype == jnp.bfloat16 else 1e-3
    np.testing.assert_allclose(np.asarray(o, np.float32), np.asarray(ow),
                               atol=tol, rtol=tol)
    np.testing.assert_allclose(np.asarray(s), np.asarray(sw),
                               atol=tol, rtol=tol)


def test_wkv6_matmul_fast_path_matches_ref():
    """Mild decay keeps every chunk on the decay-rescaled-matmul path
    (in-chunk range << SAFE_DECAY_RANGE); parity vs the exact recurrence."""
    rng = np.random.default_rng(1)
    B, T, H, K = 2, 128, 2, 32
    q = _rand(rng, (B, T, H, K), jnp.float32)
    k = _rand(rng, (B, T, H, K), jnp.float32)
    v = _rand(rng, (B, T, H, K), jnp.float32)
    ld = jnp.full((B, T, H, K), -0.01, jnp.float32)   # range 0.64 per chunk
    for u in (None, jnp.asarray(rng.standard_normal((H, K)), jnp.float32)):
        o, s = ops.wkv6(q, k, v, ld, u, chunk=64)
        ow, sw = ref.wkv6_ref(q, k, v, ld, u)
        np.testing.assert_allclose(np.asarray(o), np.asarray(ow),
                                   atol=1e-3, rtol=1e-3)
        np.testing.assert_allclose(np.asarray(s), np.asarray(sw),
                                   atol=1e-3, rtol=1e-3)


def test_wkv6_extreme_decay_uses_masked_fallback():
    """Near-maximal decay (range ~ 12*chunk >> SAFE_DECAY_RANGE) must take
    the pairwise fallback and stay finite + exact."""
    rng = np.random.default_rng(2)
    B, T, H, K = 1, 128, 1, 16
    q = _rand(rng, (B, T, H, K), jnp.float32)
    k = _rand(rng, (B, T, H, K), jnp.float32)
    v = _rand(rng, (B, T, H, K), jnp.float32)
    ld = jnp.full((B, T, H, K), -11.5, jnp.float32)
    o, s = ops.wkv6(q, k, v, ld, None, chunk=64)
    assert np.isfinite(np.asarray(o)).all()
    ow, sw = ref.wkv6_ref(q, k, v, ld, None)
    np.testing.assert_allclose(np.asarray(o), np.asarray(ow),
                               atol=1e-3, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(s), np.asarray(sw),
                               atol=1e-3, rtol=1e-3)


def test_wkv6_long_sequence_stability():
    """Decay products over 4k tokens must not overflow/underflow."""
    rng = np.random.default_rng(0)
    B, T, H, K = 1, 4096, 1, 16
    q = _rand(rng, (B, T, H, K), jnp.float32)
    k = _rand(rng, (B, T, H, K), jnp.float32)
    v = _rand(rng, (B, T, H, K), jnp.float32)
    ld = jnp.asarray(-np.exp(rng.standard_normal((B, T, H, K))),
                     jnp.float32)
    o, s = ops.wkv6(q, k, v, ld, None, chunk=64)
    assert np.isfinite(np.asarray(o)).all()
    assert np.isfinite(np.asarray(s)).all()


# ---------------------------------------------------------------- rmsnorm
@given(rows=st.sampled_from([1, 17, 300]),
       d=st.sampled_from([128, 256, 512]),
       dtype=st.sampled_from([jnp.float32, jnp.bfloat16]))
def test_rmsnorm_matches_oracle(rows, d, dtype):
    rng = np.random.default_rng(rows + d)
    x = _rand(rng, (rows, d), dtype)
    sc = _rand(rng, (d,), jnp.float32)
    out = ops.rmsnorm(x, sc)
    want = ref.rmsnorm_ref(x, sc)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               atol=_tol(dtype), rtol=_tol(dtype))


# ------------------------------------------------------- flash backward
@given(heads=st.sampled_from([(2, 2), (4, 2)]),
       causal=st.booleans(),
       window=st.sampled_from([0, 96]))
def test_flash_attention_grads_match_oracle(heads, causal, window):
    """custom_vjp backward (Pallas dq/dkv kernels) vs dense-reference
    autodiff grads."""
    if window and not causal:
        window = 0
    hq, hkv = heads
    rng = np.random.default_rng(hq * 13 + window)
    B, S, D = 1, 256, 64
    q = _rand(rng, (B, S, hq, D), jnp.float32) * 0.5
    k = _rand(rng, (B, S, hkv, D), jnp.float32) * 0.5
    v = _rand(rng, (B, S, hkv, D), jnp.float32) * 0.5
    ct = _rand(rng, (B, S, hq, D), jnp.float32)

    def loss_pl(q, k, v):
        return (ops.flash_attention(q, k, v, causal=causal,
                                    window=window) * ct).sum()

    def loss_ref(q, k, v):
        return (ref.flash_attention_ref(q, k, v, causal=causal,
                                        window=window) * ct).sum()

    g_pl = jax.grad(loss_pl, argnums=(0, 1, 2))(q, k, v)
    g_rf = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_pl, g_rf):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-4, rtol=5e-4)
