"""RWKV-6 "Finch" in the program against the plain float32 reference of
``benchmark/reference_rwkv6.py``, on seeded random weights at a tiny size
(d = 128, 2 heads of 64, 2 layers, vocabulary 512, 128 tokens: two wkv
chunks of 64), all in float32 on the CPU.

Compared: the logits of a prefill through the chunked wkv, the loss, each
leaf's gradient, one AdamW step, and the logits of decode steps through
the recurrent state after the prefill. The time mix as it was before
Finch's (a static token shift, no adapter, a clipped decay, GroupNorm eps
1e-5) fails the same tolerances.
"""
from __future__ import annotations

import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1] / "benchmark"
sys.path.insert(0, str(BENCH))

import calibrate_rwkv6 as C  # noqa: E402
import reference as R  # noqa: E402
import reference_rwkv6 as R6  # noqa: E402
import weights_rwkv6 as W6  # noqa: E402
from loops import train_rwkv6 as T  # noqa: E402

from repro.configs.base import RunConfig, ShapeConfig  # noqa: E402
from repro.models import Runtime, build, ssm  # noqa: E402
from repro.runtime.steps import make_optimizer  # noqa: E402

SEED, B, S, DECODE = 3000000017, 2, 128, 8
# Both sides compute in float32 and differ only in the order of their
# sums: the chunked wkv against the per-token recurrence, and XLA's fusion
# of each. Sound readings are 1.5e-6 relative at most; the pre-Finch time
# mix reads 2.4e-3 (the loss) to 1.3 (the AdamW step). Each gap is
# relative: to the largest reference logit, the reference loss, each
# leaf's gradient norm and each leaf's AdamW change.
TOL = {"prefill_logits": 1e-4, "loss": 1e-4, "grad": 1e-4, "adamw": 1e-3,
       "decode_logits": 1e-4}
# One AdamW step moves each weight by about lr * g / (|g| + eps): where
# |g| is near eps that ratio carries the gradient's round-off, so the step
# is held to a looser 1e-3 of each leaf's change (sound: 3.9e-4).


@pytest.fixture(scope="module")
def tiny():
    cfg = json.loads((BENCH / "configs" / "rwkv6-3b.train.json").read_text())
    cfg.update(hidden_size=128, attention_hidden_size=128,
               intermediate_size=256, vocab_size=512, num_hidden_layers=2,
               dtype="float32", wkv=dict(cfg["wkv"], chunk=64))
    model = build(T.program_config(cfg), Runtime(ssm_factored=True),
                  param_dtype=jnp.float32)
    params = W6.make_params(cfg, SEED, "float32")
    rows = np.random.default_rng(SEED).integers(
        0, cfg["vocab_size"], (B, S + DECODE + 1), dtype=np.int32)
    return cfg, model, params, jnp.asarray(rows)


def _rel(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def _gaps(cfg, model, params, rows) -> dict:
    """Each compared number's gap between program and reference."""
    fz = R._frozen(cfg)
    tokens, labels = rows[:, :S], rows[:, 1:S + 1]
    batch = {"tokens": tokens, "labels": labels}
    ref_logits = R6.logits_jit(params, rows[:, :S + DECODE], fz, "f32")
    scale = float(jnp.abs(ref_logits).max())

    # fresh functions, traced afresh: jit would reuse another time mix's
    prefill = jax.jit(lambda p, b: model.prefill(p, b, S + DECODE))
    decode = jax.jit(lambda *a: model.decode_step(*a))
    logits, caches = prefill(params, {"tokens": tokens})
    out = {"prefill_logits": float(jnp.abs(
        logits[:, 0] - ref_logits[:, S - 1]).max()) / scale}
    worst = 0.0
    for i in range(DECODE):
        logits, caches = decode(
            params, caches, rows[:, S + i:S + i + 1], jnp.int32(S + i))
        worst = max(worst, float(jnp.abs(
            logits[:, 0] - ref_logits[:, S + i]).max()) / scale)
    out["decode_logits"] = worst

    (loss, _), grads = jax.jit(jax.value_and_grad(
        lambda p, b: model.loss(p, b), has_aux=True))(params, batch)
    ref_loss, ref_grads = R6.loss_and_grad(params, tokens, labels, fz, "f32")
    out["loss"] = abs(float(loss) - float(ref_loss)) / float(ref_loss)
    out["grad"] = max(_rel(g, r) for g, r in zip(jax.tree.leaves(grads),
                                                  jax.tree.leaves(ref_grads)))

    opt_cfg = cfg["optimizer"]
    opt = make_optimizer(RunConfig(
        model=model.cfg, shape=ShapeConfig("t", "train", S, B),
        learning_rate=opt_cfg["lr"], warmup_steps=opt_cfg["warmup_steps"],
        weight_decay=opt_cfg["weight_decay"], grad_clip=opt_cfg["grad_clip"]),
        opt_cfg["total_steps"])
    stepped, _, _ = jax.jit(opt.update)(grads, opt.init(params), params)
    # the reference's update donates the weights it is given
    ref_stepped, _, _ = R.adamw(jax.tree.map(jnp.copy, params), ref_grads,
                                None, 1, opt_cfg)
    out["adamw"] = max(
        _rel(np.asarray(p) - np.asarray(w), np.asarray(r) - np.asarray(w))
        for p, r, w in zip(jax.tree.leaves(stepped),
                           jax.tree.leaves(ref_stepped),
                           jax.tree.leaves(params))
        if float(jnp.abs(jnp.asarray(r) - w).max()) > 0)
    return out


def test_program_matches_the_reference(tiny):
    gaps = _gaps(*tiny)
    for name, tol in TOL.items():
        assert gaps[name] <= tol, (name, gaps)


def test_pre_finch_time_mix_fails_the_tolerances(tiny):
    with C.pre_finch():
        gaps = _gaps(*tiny)
    assert any(gaps[name] > 100 * tol for name, tol in TOL.items()), gaps


def test_decode_shares_the_time_mix(tiny, monkeypatch):
    cfg, model, params, rows = tiny
    calls = []
    sound = ssm.rwkv6_time_mix

    def counted(*a, **k):
        calls.append(k["backend"])
        return sound(*a, **k)

    monkeypatch.setattr(ssm, "rwkv6_time_mix", counted)
    _, caches = model.prefill(params, {"tokens": rows[:, :4]}, 8)
    model.decode_step(params, caches, rows[:, 4:5], jnp.int32(4))
    assert calls == ["chunked", "recurrent"]      # traced once per scan


def test_decay_is_unclipped_and_eps_is_the_configs(tiny):
    cfg, model, params, rows = tiny
    p = jax.tree.map(lambda a: a[0], params["layers"]["time_mix"])
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 4, 128))
    # base decays whose log decays lie beyond the old clip, [-12, -1e-4]
    low = dict(p, w0=jnp.full_like(p["w0"], 4.0))
    high = dict(p, w0=jnp.full_like(p["w0"], -14.0))
    assert float(ssm.rwkv6_mix_inputs(low, x, x, model.cfg)[-1].min()) < -12
    assert float(ssm.rwkv6_mix_inputs(high, x, x, model.cfg)[-1].max()) \
        > -1e-4
    assert model.cfg.ssm.group_norm_eps == pytest.approx(6.4e-4)
    assert (model.cfg.ssm.mix_rank, model.cfg.ssm.decay_rank) == (32, 64)
