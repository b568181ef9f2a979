"""The Pallas flash-attention backward kernels' (dq, and dk with dv) share
of their roofline: the least work of each backward pass in the window on
one device's share of the batch and heads, at the chip's peaks, over the
two kernels' device time."""
from roofline import flash_attention as K
from roofline.share import roofline_share


def read(run):
    w, t = run.work, run.trace
    if t is None or "seq" not in w:
        return None
    passes = t.kernel_calls(K.BACKWARD) / K.KERNELS_PER_BACKWARD
    flops, nbytes = K.backward_cost(run.cfg, w["batch"] / w["chips"],
                                    w["seq"])
    return roofline_share(t, K.BACKWARD, flops * passes, nbytes * passes,
                          run.peaks)
