"""Model FLOPs of the window's completed steps (forward and backward of
every sequence, causal attention included, recomputation not counted) over
the window's wall time, as a share of the peak of the chips used."""
from roofline.model import train_flops_per_sequence
from stats import share


def read(run):
    w = run.work
    if "train_steps" not in w:
        return None
    flops = (train_flops_per_sequence(run.cfg, w["seq"]) * w["batch"]
             * w["train_steps"])
    return share(flops / run.window_s,
                 w["chips"] * run.peaks["bf16_flops_per_s"])
