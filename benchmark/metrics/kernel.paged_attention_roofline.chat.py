"""The Pallas paged decode kernel's share of its roofline over the window:
the least work of every decode row (all query heads over its live keys)
at the chip's peaks, over the kernel's device time in the trace."""
from roofline import paged_attention as K
from roofline.share import roofline_share


def read(run):
    rows = run.work.get("decode_rows")
    if not rows:
        return None
    flops, nbytes = K.cost(run.cfg, rows)
    return roofline_share(run.trace, K.KERNEL, flops, nbytes, run.peaks)
