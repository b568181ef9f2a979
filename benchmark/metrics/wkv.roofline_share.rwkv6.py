"""The wkv recurrence's share of its roofline: its least work over the
window's train steps (``roofline.rwkv6.wkv_cost``) at the chip's peaks,
over the device seconds the trace puts under the ``wkv`` scope of the
train step (forward, recomputed and backward), per chip."""
from roofline.rwkv6 import wkv_cost


def read(run):
    w = run.work
    secs = (w.get("train_step_scope_s") or {}).get("wkv")
    if not secs or "train_steps" not in w:
        return None
    tokens = w["seq"] * w["batch"] * w["train_steps"] / w["chips"]
    flops, nbytes = wkv_cost(run.cfg, tokens)
    least = max(flops / run.peaks["bf16_flops_per_s"],
                nbytes / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / secs
