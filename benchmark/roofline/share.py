"""A kernel's share of its roofline: the least time the chip could take
for the work, the larger of operations over peak FLOP/s and bytes over
peak bandwidth, over the kernel's summed device time in the trace."""
from __future__ import annotations


def roofline_share(trace, pattern: str, flops: float, nbytes: float,
                   peaks: dict):
    """Percent; None where the trace holds no time for the kernel."""
    if trace is None:
        return None
    t = trace.kernel_s(pattern)
    if t <= 0 or (flops <= 0 and nbytes <= 0):
        return None
    least = max(flops / peaks["bf16_flops_per_s"],
                nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * least / t
