"""The Pallas flash-attention kernels (``kernels/flash_attention.py``),
causal, on one device's share of the rows and heads. A forward call's
least work is the two matmuls over the causal half of the scores, 4 * Hq *
D * S(S+1)/2 operations a row, and reading q, k, v and writing o once. A
backward pass (the dq and the dk/dv kernels together) needs twice the
forward's matmuls, and reads q, k, v, o and do and writes dq, dk, dv once.

Calls are counted from the trace: the forward kernel runs again where the
step recomputes a layer, and that work is the kernel's too. Inside a
jitted step every flash kernel may carry the same instruction name, so a
call is told by its operands: the forward kernel takes q, k and v, each
backward kernel q, k, v, do, lse and delta."""
from __future__ import annotations

import weights as W

FORWARD = r"flash_attention.*#3$"
BACKWARD = r"flash_attention.*#6$"
KERNELS_PER_BACKWARD = 2          # the dq kernel and the dk/dv kernel
BYTES = {"bfloat16": 2, "float32": 4}


def forward_cost(cfg: dict, rows: int, seq: int) -> tuple:
    """(flops, bytes) of one forward call over ``rows`` rows of ``seq``."""
    m = W.dims(cfg)
    b = BYTES[cfg["dtype"]]
    flops = 4 * m["hq"] * m["hd"] * seq * (seq + 1) / 2 * rows
    nbytes = rows * seq * m["hd"] * (2 * m["hq"] + 2 * m["hkv"]) * b
    return flops, nbytes


def backward_cost(cfg: dict, rows: int, seq: int) -> tuple:
    """(flops, bytes) of one backward pass over ``rows`` rows of ``seq``."""
    m = W.dims(cfg)
    b = BYTES[cfg["dtype"]]
    flops = 2 * forward_cost(cfg, rows, seq)[0]
    nbytes = rows * seq * m["hd"] * (4 * m["hq"] + 4 * m["hkv"]) * b
    return flops, nbytes
