"""The Pallas flash-attention forward kernel's share of its roofline: the
least work of each of its calls in the window on one device's share of
the batch and heads, at the chip's peaks, over its device time."""
from roofline import flash_attention as K
from roofline.share import roofline_share


def read(run):
    w, t = run.work, run.trace
    if t is None or "seq" not in w:
        return None
    calls = t.kernel_calls(K.FORWARD)
    flops, nbytes = K.forward_cost(run.cfg, w["batch"] / w["chips"], w["seq"])
    return roofline_share(t, K.FORWARD, flops * calls, nbytes * calls,
                          run.peaks)
