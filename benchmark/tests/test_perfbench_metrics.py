"""The metric arithmetic: failures count as misses in the tails, rates
take all the work over the whole window, and MFU and roofline shares
match hand counts at small shapes. The trace reduction is checked on a
small trace recorded on a TPU v5e (``data/small.xplane.pb``: a jitted
matmul, the paged decode kernel, and the flash kernels forward and
backward, twice each, inside host spans)."""
from __future__ import annotations

import math
from types import SimpleNamespace

import pytest

from tiny_cells import BENCH

import run
import xplane
from roofline import flash_attention as flash
from stats import MISS_S, percentile

FIXTURE = str(BENCH / "tests" / "data" / "small.xplane.pb")
PEAKS = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
CFG = {"hidden_size": 8, "intermediate_size": 16, "num_attention_heads": 2,
       "num_key_value_heads": 1, "head_dim": 4, "num_hidden_layers": 3,
       "vocab_size": 10, "dtype": "bfloat16"}


def metric(name, **ctx):
    base = dict(work={}, trace=None, peaks=PEAKS, cfg=CFG, setup_s=1.0,
                window_s=2.0)
    base.update(ctx)
    return run.load_module(BENCH / "metrics" / f"{name}.py").read(
        SimpleNamespace(**base))


def test_percentile_is_nearest_rank():
    vals = list(range(1, 21))              # 1..20
    assert percentile(vals, 95) == 19
    assert percentile(vals, 50) == 10
    assert percentile([], 95) is None


def test_failed_requests_are_misses_in_the_tail():
    ok = [0.1] * 9 + [0.3] * 11
    assert metric("ttft_p50_s", work={"ttft_s": ok}) == 0.3
    assert metric("ttft_p50_s", work={"ttft_s": [0.1] * 10 + [math.inf] * 10}
                  ) == 0.1
    assert metric("ttft_p50_s", work={"ttft_s": [0.1] * 9 + [math.inf] * 11}
                  ) == MISS_S
    queue = [0.1] * 18
    assert metric("engine.queue_wait_p90_s.chat",
                  work={"queue_s": queue + [math.inf] * 2}) == 0.1
    assert metric("engine.queue_wait_p90_s.chat",
                  work={"queue_s": queue[:17] + [math.inf] * 3}) == MISS_S


def test_itl_takes_every_gap():
    gaps = [0.01] * 94 + [0.5] * 6
    assert metric("itl_p95_ms", work={"itl_s": gaps}) == pytest.approx(500)


def test_rates_are_all_work_over_the_whole_window():
    work = {"train_tokens": 8192 * 10, "train_steps": 10, "seq": 4096,
            "batch": 2, "chips": 1}
    assert metric("train_tokens_per_s", work=work, window_s=4.0) == 20480.0
    assert metric("setup_s") == 1.0


def _flops_per_token(keys):
    d, f, hq, hkv, hd, L, V = 8, 16, 2, 1, 4, 3, 10
    layer = d * hq * hd + 2 * d * hkv * hd + hq * hd * d + 3 * d * f
    return 2 * (L * layer + d * V) + 4 * hq * hd * keys * L


def test_mfu_matches_hand_counts():
    # one request: prompt 5, 3 tokens -> decode steps at 6 and 7 keys
    work = {"decode_s": [0.5, 1.5], "decode_rows": [(5, 3)]}
    want = (_flops_per_token(6) + _flops_per_token(7)) / 2.0 / 100.0 * 100
    assert metric("mfu.decode_step.chat", work=work) == pytest.approx(want)
    seq = 4
    layer = 8 * 8 + 2 * 8 * 4 + 8 * 8 + 3 * 8 * 16
    per_seq = 3 * (2 * (3 * layer + 8 * 10) * seq + 4 * 2 * 4 * 10 * 3)
    work = {"train_steps": 2, "seq": seq, "batch": 3, "chips": 2}
    want = per_seq * 3 * 2 / 2.0 / (2 * 100.0) * 100
    assert metric("mfu.train_step", work=work) == pytest.approx(want)


class FakeTrace:
    def __init__(self, seconds, calls):
        self.seconds, self.calls = seconds, calls

    def kernel_s(self, pattern):
        return self.seconds

    def kernel_calls(self, pattern):
        return self.calls


def test_roofline_shares_match_hand_counts():
    # paged: rows (5, 3) -> keys 6 and 7, Hq=2, Hkv=1, D=4, 3 layers, bf16
    flops = 4 * 2 * 4 * (6 + 7) * 3
    nbytes = (2 * (6 + 7) * 1 * 4 * 2 + 2 * 2 * 2 * 4 * 2) * 3
    least = max(flops / 100.0, nbytes / 10.0)
    got = metric("kernel.paged_attention_roofline.chat",
                 work={"decode_rows": [(5, 3)]}, trace=FakeTrace(400.0, 6))
    assert got == pytest.approx(100 * least / 400.0)
    # flash forward: 3 calls over 1 row (2 rows over 2 chips) of seq 4
    work = {"seq": 4, "batch": 2, "chips": 2}
    f = 4 * 2 * 4 * 10 * 1
    b = 1 * 4 * 4 * (2 * 2 + 2 * 1) * 2
    least = 3 * max(f / 100.0, b / 10.0)
    got = metric("kernel.flash_fwd_roofline.train", work=work,
                 trace=FakeTrace(60.0, 3))
    assert got == pytest.approx(100 * least / 60.0)
    # backward: 4 kernel calls are 2 passes of dq + dk/dv
    b = 1 * 4 * 4 * (4 * 2 + 4 * 1) * 2
    least = 2 * max(2 * f / 100.0, b / 10.0)
    got = metric("kernel.flash_bwd_roofline.train", work=work,
                 trace=FakeTrace(60.0, 4))
    assert got == pytest.approx(100 * least / 60.0)


def test_metrics_are_silent_without_their_input():
    for name in ("kernel.paged_attention_roofline.chat", "mfu.train_step",
                 "device.idle_share.train", "kernel.flash_fwd_roofline.train",
                 "itl_p95_ms", "train_tokens_per_s"):
        assert metric(name) is None, name


def test_token_times_follow_the_decode_steps():
    serve = run.load_module(BENCH / "loops" / "serve.py")
    m = SimpleNamespace(rid=0, first_token_s=1.0, new_tokens=3,
                        finish_s=1.31)
    # a prefill ran between the two decode steps after the first token
    assert serve.token_times(m, [0.5, 1.1, 1.3, 1.4]) == [1.0, 1.1, 1.3]
    # the host was held up after the last step: the finish reads late
    m.finish_s = 1.39
    assert serve.token_times(m, [0.5, 1.1, 1.3, 1.4]) == [1.0, 1.1, 1.3]
    with pytest.raises(RuntimeError):
        serve.token_times(m, [0.5, 1.1])


@pytest.fixture(scope="module")
def reduced():
    return xplane.reduce_trace(FIXTURE)


def test_trace_kernel_times(reduced):
    assert reduced.devices == 1
    assert reduced.kernel_s("paged_attention") == pytest.approx(
        (25871 + 25777) * 1e-9)
    assert reduced.kernel_calls("paged_attention") == 2
    assert reduced.kernel_calls(flash.FORWARD) == 2
    assert reduced.kernel_calls(flash.BACKWARD) == 4
    assert reduced.kernel_s(flash.FORWARD) == pytest.approx(
        (621278 + 621277) * 1e-9)
    assert sorted(reduced.custom_call_n) == [
        "_paged_attention_jit#5", "jvp_jit__flash_attention__#3",
        "transpose_jvp_jit__flash_attention___#6"]


@pytest.mark.parametrize("text,want", [
    ("%while.3 = (s32[], bf16[2,3]{1,0}, (f32[1], f32[2])) while((s32[], "
     "bf16[2,3]{1,0}) %tuple.1), condition=%c, body=%b", ("while", 1)),
    ("%fusion.2 = bf16[8,32]{1,0} fusion(bf16[8,32]{1,0} %a, f32[] %b), "
     "kind=kLoop", ("fusion", 2)),
    ("%all-reduce-start = f32[8]{0} all-reduce-start(f32[8]{0} %x), "
     "replica_groups={}", ("all-reduce-start", 1)),
])
def test_parse_op(text, want):
    assert xplane.parse_op(text) == want


def test_trace_busy_idle_and_gaps(reduced):
    assert 0 < reduced.busy_s < reduced.window_s
    idle = 100 * (reduced.window_s - reduced.busy_s) / reduced.window_s
    assert metric("device.idle_share.chat", trace=reduced) == \
        pytest.approx(idle)
    ops = dict(reduced.top_ops(20))
    assert sum(ops.values()) >= reduced.busy_s * 0.99
    assert reduced.collective_s == 0.0
    names = [n for n, _ in reduced.idle_gaps]
    assert names and all(isinstance(n, str) for n in names)
    assert any(n.startswith("probe.") or n.startswith("PjitFunction")
               for n in names)
    secs = [t for _, t in reduced.idle_gaps]
    assert secs == sorted(secs, reverse=True)


def test_trace_window_from_a_host_span():
    whole = xplane.reduce_trace(FIXTURE, "probe.flash")
    assert 0 < whole.window_s < 0.005
    assert whole.kernel_calls("paged_attention") == 0
