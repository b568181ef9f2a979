"""The Pallas paged decode kernel (``kernels/paged_attention.py``): one
query token per row against its live keys, at every query head. Its least
work per row and layer is 4 * Hq * D * len operations and reading len keys
and values of the Hkv heads once, plus the query and the output."""
from __future__ import annotations

import weights as W

KERNEL = r"paged_attention"
BYTES = {"bfloat16": 2, "float32": 4}


def cost(cfg: dict, rows) -> tuple:
    """(flops, bytes) over all decode steps of requests given as
    (prompt_len, new_tokens), every layer."""
    m = W.dims(cfg)
    b = BYTES[cfg["dtype"]]
    flops = nbytes = 0.0
    for plen, n in rows:
        for j in range(1, n):
            keys = plen + j
            flops += 4 * m["hq"] * m["hd"] * keys
            nbytes += 2 * keys * m["hkv"] * m["hd"] * b \
                + 2 * m["hq"] * m["hd"] * b
    return flops * m["L"], nbytes * m["L"]
