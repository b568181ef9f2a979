"""Per-architecture smoke tests: every assigned arch instantiates a REDUCED
config of the same family and runs forward + one train step + prefill +
decode on CPU, asserting output shapes and finiteness."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import ARCHS, SHAPES, MeshConfig, RunConfig, ShapeConfig, \
    reduced
from repro.models import build, Runtime
from repro.models.frontends import synth_batch

ARCH_IDS = sorted(ARCHS)


@pytest.fixture(scope="module")
def built():
    cache = {}

    def get(name):
        if name not in cache:
            cfg = reduced(ARCHS[name])
            rt = Runtime(attention_backend="dense", chunk=32)
            model = build(cfg, rt, param_dtype=jnp.float32)
            params = model.init_params(jax.random.PRNGKey(0))
            cache[name] = (cfg, model, params)
        return cache[name]

    return get


@pytest.mark.parametrize("name", ARCH_IDS)
def test_forward_loss(built, name):
    cfg, model, params = built(name)
    batch = synth_batch(cfg, 2, 32, kind="train")
    loss, aux = jax.jit(model.loss)(params, batch)
    assert np.isfinite(float(loss))
    assert float(loss) > 0


@pytest.mark.parametrize("name", ARCH_IDS)
def test_train_step(built, name):
    cfg, model, params = built(name)
    mesh = MeshConfig(shape=(1, 1), axes=("data", "model"))
    rcfg = RunConfig(model=cfg, mesh=mesh, param_dtype="float32",
                     attention_backend="dense",
                     shape=ShapeConfig("t", "train", 32, 2), microbatches=1)
    from repro.runtime.steps import build_train_step
    step, model2, opt = build_train_step(rcfg)
    params2 = model2.init_params(jax.random.PRNGKey(0))
    opt_state = opt.init(params2)
    batch = synth_batch(cfg, 2, 32, kind="train")
    p3, o3, metrics = jax.jit(step)(params2, opt_state, batch)
    assert np.isfinite(float(metrics["loss"]))
    # params actually changed
    delta = jax.tree.reduce(
        lambda a, b: a + b,
        jax.tree.map(lambda a, b: float(jnp.abs(a - b).sum()), params2, p3))
    assert delta > 0


@pytest.mark.parametrize("name", ARCH_IDS)
def test_prefill_decode_shapes(built, name):
    cfg, model, params = built(name)
    B, S = 2, 32
    batch = synth_batch(cfg, B, S, kind="prefill")
    logits, caches = jax.jit(lambda p, b: model.prefill(p, b, S + 4))(
        params, batch)
    assert logits.shape == (B, 1, cfg.vocab_size)
    assert np.isfinite(np.asarray(logits)).all()
    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    logits2, caches2 = jax.jit(model.decode_step)(params, caches, tok,
                                                  jnp.int32(S))
    assert logits2.shape == (B, 1, cfg.vocab_size)
    assert np.isfinite(np.asarray(logits2)).all()


@pytest.mark.parametrize("name", ["qwen2.5-32b", "stablelm-12b",
                                  "granite-3-8b", "whisper-large-v3",
                                  "hymba-1.5b", "rwkv6-3b", "arctic-480b"])
def test_decode_matches_teacher_forcing(name):
    """Incremental decode after prefill == teacher-forced forward."""
    import dataclasses
    cfg = reduced(ARCHS[name])
    if cfg.moe is not None:  # no-drop capacity => exact equality
        cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
            cfg.moe, capacity_factor=float(cfg.moe.num_experts) / cfg.moe.top_k))
    rt = Runtime(attention_backend="dense", chunk=16)
    model = build(cfg, rt, param_dtype=jnp.float32)
    params = model.init_params(jax.random.PRNGKey(1))
    B, S = 2, 24
    batch = synth_batch(cfg, B, S + 1, seed=3, kind="prefill")
    pf = {k: (v if k == "audio_embeds" else v[:, :S]) for k, v in batch.items()}
    _, caches = model.prefill(params, pf, S + 8)
    tok = batch["tokens"][:, S:S + 1]
    logits_dec, _ = model.decode_step(params, caches, tok, jnp.int32(S))
    # teacher-forced logits at position S come from loss-path structure:
    full = synth_batch(cfg, B, S + 1, seed=3, kind="train")
    full["tokens"] = batch["tokens"]
    if "audio_embeds" in batch:
        full["audio_embeds"] = batch["audio_embeds"]
    # reuse prefill on S+1 tokens: its last-position logits == teacher forced
    logits_full, _ = model.prefill(params, batch, S + 9)
    rel = float(jnp.abs(logits_dec - logits_full).max()) / (
        float(jnp.abs(logits_full).max()) + 1e-9)
    assert rel < 5e-3, rel


def test_full_configs_match_assignment():
    """Exact assigned hyperparameters are preserved in the full configs."""
    a = ARCHS["qwen2.5-32b"]
    assert (a.num_layers, a.d_model, a.num_heads, a.num_kv_heads,
            a.d_ff, a.vocab_size) == (64, 5120, 40, 8, 27648, 152064)
    assert a.qkv_bias
    b = ARCHS["arctic-480b"]
    assert b.moe.num_experts == 128 and b.moe.top_k == 2
    assert b.moe.dense_residual_ff == 4864
    c = ARCHS["rwkv6-3b"]
    assert c.attention_kind == "none" and c.ssm.kind == "rwkv6"
    # Finch's adapter ranks and GroupNorm eps (1e-5 * head_size_divisor**2)
    assert (c.ssm.mix_rank, c.ssm.decay_rank) == (32, 64)
    assert c.ssm.group_norm_eps == pytest.approx(6.4e-4)
    d = ARCHS["hymba-1.5b"]
    assert d.ssm is not None and d.attention_kind == "sliding"
    w = ARCHS["whisper-large-v3"]
    assert w.encoder_layers == 32 and w.is_enc_dec
    v = ARCHS["qwen2-vl-72b"]
    assert v.rope == "mrope"
    assert len(ARCHS) == 10


def test_shape_cells_accounting():
    """40 assigned cells = 32 runnable + 8 noted long_500k skips."""
    from repro.configs import cells
    runnable = cells()
    assert len(runnable) == 32
    skipped = [a.name for a in ARCHS.values() if not a.sub_quadratic]
    assert len(skipped) == 8
    assert len(ARCHS) * len(SHAPES) == 40
