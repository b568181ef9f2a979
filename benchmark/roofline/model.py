"""Operations a dense GQA decoder needs, counted from shapes: the matmuls
a token passes through (its layers' projections and MLP, and the tied
head over the published vocabulary) and causal attention over the keys it
sees. Recomputation and padding are not counted, nor are the extra key
heads a kernel may score."""
from __future__ import annotations

import weights as W


def token_flops(cfg: dict, keys: int) -> float:
    """Forward FLOPs of one token that attends over ``keys`` keys."""
    m = W.dims(cfg)
    p = W.param_count(cfg)
    attn = 4 * m["hq"] * m["hd"] * keys * m["L"]
    return 2 * (p["layers"] + p["head"]) + attn


def decode_flops(cfg: dict, rows) -> float:
    """All decode steps of requests given as (prompt_len, new_tokens): the
    step that makes token j (j >= 1) attends over prompt_len + j keys."""
    total = 0.0
    for plen, n in rows:
        for j in range(1, n):
            total += token_flops(cfg, plen + j)
    return total


def train_flops_per_sequence(cfg: dict, seq: int) -> float:
    """Forward and backward (three forwards) of one causal sequence."""
    m = W.dims(cfg)
    p = W.param_count(cfg)
    attn = 4 * m["hq"] * m["hd"] * (seq * (seq + 1) / 2) * m["L"]
    return 3 * (2 * (p["layers"] + p["head"]) * seq + attn)
