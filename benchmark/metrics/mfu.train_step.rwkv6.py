"""Model FLOPs of the window's completed rwkv6 train steps (every
projection, adapter, channel mix, the head and the wkv, forward and
backward, recomputation not counted) over the window's wall time, as a
share of the bf16 peak of the chips used."""
from roofline.rwkv6 import train_flops_per_token
from stats import share


def read(run):
    w = run.work
    if "train_steps" not in w:
        return None
    flops = (train_flops_per_token(run.cfg) * w["seq"] * w["batch"]
             * w["train_steps"])
    return share(flops / run.window_s,
                 w["chips"] * run.peaks["bf16_flops_per_s"])
