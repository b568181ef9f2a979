"""Model FLOPs of every decode step of the window (each row at its own
key count) over the decode dispatches' host time, as a share of the
chip's bf16 peak."""
from roofline.model import decode_flops
from stats import share


def read(run):
    d = run.work.get("decode_s")
    if not d:
        return None
    rate = decode_flops(run.cfg, run.work["decode_rows"]) / sum(d)
    return share(rate, run.peaks["bf16_flops_per_s"])
