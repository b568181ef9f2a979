"""The rwkv6 training cell at a tiny size on the CPU, past the harness's
look for a chip: a sound run is correct, and a run whose timed step runs
the time mix as it was before Finch's, or leaves the state unchanged, is
not; the float8 control fails the cell's limits. Its three per-layer
metrics against hand counts, and the named scopes of its train step:
``time_mix``, ``wkv`` inside it, and ``channel_mix``, forward and
backward."""
from __future__ import annotations

import json
import re
import shutil
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import pytest

from tiny_cells import BENCH, execute, loop_module, make_root

import calibrate_rwkv6 as C
import program_trace as P
import run
import scopes
from repro.models import ssm

CELL = "rwkv6-3b.train.4k"
TINY = dict(hidden_size=128, attention_hidden_size=128,
            intermediate_size=256, vocab_size=512, num_hidden_layers=2)
# Limits for the tiny model, set as the cell's own are at its real size:
# above what sound tiny runs read (loss, gradient and change gaps at most
# 1.25e-3, 0.157 and 6.2e-3 over four seeds: the bonus u's gradient is
# the widest, as rounding r, k and v to bfloat16 moves it) and below the
# faults' readings (the float8 control's loss gap 5.8e-3 and more; the
# pre-Finch time mix 0.026, 1 and 0.98; a state left unchanged 1).
CHECK = {"loss_gap": 3e-3, "grad_norm_gap": 0.5, "update_norm_gap": 0.02}
PEAKS = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
SPANS = BENCH / "tests" / "data" / "spans.xplane.pb"


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = make_root(tmp_path_factory.mktemp("tiny"))
    conf = root / "benchmark" / "configs" / "rwkv6-3b.train.json"
    cfg = json.loads(conf.read_text())
    cfg.update(TINY, check=CHECK)
    conf.write_text(json.dumps(cfg))
    traffic = root / "benchmark" / "traffic" / "train-4k.rwkv6.json"
    traffic.write_text(json.dumps(dict(json.loads(traffic.read_text()),
                                       seq=128)))
    return root


def _unchanged(step):
    def bad(params, opt_state, batch):
        return (params, opt_state, step(params, opt_state, batch)[2])
    return bad


@pytest.mark.parametrize("fault", ["sound", "pre_finch", "state_unchanged"])
def test_run_is_correct_only_when_sound(root, fault, monkeypatch):
    train = loop_module("train_rwkv6")
    if fault == "pre_finch":
        monkeypatch.setattr(ssm, "rwkv6_time_mix", C.pre_finch_time_mix)
    if fault == "state_unchanged":
        make = train.program_step

        def broken(*a, **k):
            out = list(make(*a, **k))
            out[1] = _unchanged(out[1])
            return tuple(out)
        monkeypatch.setattr(train, "program_step", broken)
    result = execute(root, CELL, seed=7, seconds=0.5)
    assert result["attempted"] >= 2
    assert result["correct"] is (fault == "sound"), result["checks"]
    assert set(result["metrics"]) == {"train_tokens_per_s", "setup_s"}


def test_control_fails_the_cells_limits(root):
    c = run.resolve(CELL, root)
    train = loop_module("train_rwkv6")
    bundle = train.build(c.cfg, c.traffic, jax.devices()[:1])
    with jax.set_mesh(bundle[0]):
        numbers = train.start(bundle, c.cfg, c.traffic, 11)[0]
    steps = c.traffic["first_steps"]
    ref = train.reference_steps(c.cfg, c.traffic, 11, steps, "f32")
    low = train.reference_steps(c.cfg, c.traffic, 11, steps, "fp8")
    program, control = train.compare(numbers, ref), train.compare(low, ref)
    assert all(program[k] <= CHECK[k] for k in CHECK), program
    assert any(control[k] > CHECK[k] for k in CHECK), control
    assert control["loss_gap"] > 3 * program["loss_gap"]


# --------------------------------------------------------------- metrics
def metric(name, cfg, **ctx):
    base = dict(work={}, trace=None, peaks=PEAKS, cfg=cfg, setup_s=1.0,
                window_s=2.0)
    base.update(ctx)
    return run.load_module(BENCH / "metrics" / f"{name}.py").read(
        SimpleNamespace(**base))


def _cfg():
    cfg = json.loads((BENCH / "configs" / "rwkv6-3b.train.json").read_text())
    cfg.update(hidden_size=8, attention_hidden_size=8, head_size=4,
               intermediate_size=16, vocab_size=10, num_hidden_layers=3,
               mix_rank=2, decay_rank=3)
    return cfg


def test_metrics_match_hand_counts():
    cfg = _cfg()
    d, f, L, V, H, K = 8, 16, 3, 10, 2, 4
    layer = 5 * d * d + 2 * d * 5 * 2 + 2 * d * 3 + 2 * d * f + d * d
    wkv = 4 * H * K * K
    per_token = 3 * (2 * (L * layer + d * V) + L * wkv)
    work = {"train_steps": 2, "seq": 4, "batch": 3, "chips": 2}
    want = per_token * 4 * 3 * 2 / 2.0 / (2 * 100.0) * 100
    assert metric("mfu.train_step.rwkv6", cfg, work=work) == \
        pytest.approx(want)
    scope_s = {"wkv": 60.0, "time_mix": 20.0, None: 20.0}
    work = dict(work, train_step_scope_s=scope_s)
    tokens = 4 * 3 * 2 / 2
    flops = 3 * wkv * tokens * L
    nbytes = 2 * 5 * tokens * L * d * 2
    least = max(flops / 100.0, nbytes / 10.0)
    assert metric("wkv.roofline_share.rwkv6", cfg, work=work) == \
        pytest.approx(100 * least / 60.0)
    assert metric("wkv.step_share.rwkv6", cfg, work=work) == \
        pytest.approx(60.0)


@pytest.mark.parametrize("name", ["mfu.train_step.rwkv6",
                                  "wkv.roofline_share.rwkv6",
                                  "wkv.step_share.rwkv6"])
def test_metrics_are_silent_without_their_input(name):
    assert metric(name, _cfg()) is None
    # a trace whose train step carries no wkv scope, as the parent's
    work = {"train_steps": 2, "seq": 4, "batch": 3, "chips": 1,
            "train_step_scope_s": {None: 5.0}}
    if name != "mfu.train_step.rwkv6":
        assert metric(name, _cfg(), work=work) is None


def test_scope_seconds_reads_the_train_step(tmp_path):
    shutil.copy(SPANS, tmp_path / "spans.xplane.pb")
    got = loop_module("train_rwkv6").scope_seconds(str(tmp_path))
    assert got == P.reduce(str(SPANS), run.WINDOW_SPAN).module_s("train_step")
    assert got.get("optimizer", 0) > 0


# ---------------------------------------------------------------- scopes
def test_scopes_claim_the_train_step_forward_and_backward(root):
    c = run.resolve(CELL, root)
    train = loop_module("train_rwkv6")
    _, _, optimizer, _, _, _, jit_step = train.build(c.cfg, c.traffic,
                                                     jax.devices()[:1])
    params = jax.eval_shape(lambda: train.W6.make_params(
        c.cfg, 1, c.cfg["dtype"]))
    rows = jax.ShapeDtypeStruct((c.traffic["batch"], c.traffic["seq"]),
                                jnp.int32)
    text = jit_step.lower(params, jax.eval_shape(optimizer.init, params),
                          {"tokens": rows, "labels": rows}
                          ).compile().as_text()
    found = {}
    for path in re.findall(r'op_name="([^"]*)"', text):
        found.setdefault((scopes.scope_of(path), "transpose(" in path), path)
    for scope in ("time_mix", "wkv", "channel_mix", "norm", "embed",
                  "lm_head", "loss"):
        assert (scope, False) in found, scope
    for scope in ("time_mix", "wkv", "channel_mix", "lm_head"):
        assert (scope, True) in found, scope
    assert ("optimizer", False) in found
    # the recurrence sits inside the time mix, forward and backward
    for backward in (False, True):
        assert re.search(r"time_mix\)*/(\w+\()*wkv",
                         found[("wkv", backward)]), found[("wkv", backward)]
