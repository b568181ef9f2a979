"""Work of the rwkv6 configuration, counted from shapes.

A token passes through every projection and adapter of the time mix (r,
k, v, g and output; the token-shift adapters, 5 of rank ``mix_rank``; the
decay adapter), the channel mix (key, value, receptance) and the untied
head over the published vocabulary: 2 operations a weight. The wkv adds,
per token, head and layer, 2 * K * V for the state update and 2 * K * V
for the readout. Training is three forwards; recomputation is not
counted, nor are the elementwise shift, decay and norms.

The wkv's least work in a train step is those operations, forward and
backward, and its least bytes reading r, k, v and w and writing o once
forward, and once more, for their gradients, backward, at the
configuration's dtype.
"""
from __future__ import annotations

import weights_rwkv6 as W6

BYTES = {"bfloat16": 2, "float32": 4}


def layer_weights(cfg: dict) -> int:
    """Weights of the matmuls one token passes through in one layer."""
    m = W6.dims(cfg)
    d, f = m["d"], m["f"]
    mix = len(W6.MIXES) * m["R"]
    return 5 * d * d + 2 * d * mix + 2 * d * m["Rd"] + 2 * d * f + d * d


def wkv_flops_per_token(cfg: dict) -> float:
    """Forward operations of the wkv for one token in one layer."""
    m = W6.dims(cfg)
    return 4 * m["H"] * m["hs"] * m["hs"]


def train_flops_per_token(cfg: dict) -> float:
    """Forward and backward (three forwards) of one token."""
    m = W6.dims(cfg)
    fwd = 2 * (m["L"] * layer_weights(cfg) + m["d"] * m["V"]) \
        + m["L"] * wkv_flops_per_token(cfg)
    return 3 * fwd


def wkv_cost(cfg: dict, tokens: float) -> tuple:
    """(flops, bytes) of the wkv over ``tokens`` tokens of train steps,
    every layer, forward and backward."""
    m = W6.dims(cfg)
    flops = 3 * wkv_flops_per_token(cfg) * tokens * m["L"]
    nbytes = 2 * 5 * tokens * m["L"] * m["d"] * BYTES[cfg["dtype"]]
    return flops, nbytes
