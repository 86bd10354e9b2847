"""Causal prefill attention: what the algorithm needs, from shapes.

One call is one layer's attention over one prompt of ``s`` real tokens
(the bucket's pad rows are not work the prompt needs).  Each of the
``heads`` query heads takes QK^T and PV over the s(s+1)/2 causal pairs at
``head_dim``: 2 flops per multiply-add, two contractions.  Bytes: q and
the output once per query head, k and v once per kv head, in the served
dtype.
"""
from __future__ import annotations

# The program it runs in, and its op's name in the trace.
PROGRAM = "prefill"
OP = "flash_attention"


def flops(sizes: dict, s: int) -> float:
    pairs = s * (s + 1) / 2
    return 2 * 2 * pairs * sizes["head_dim"] * sizes["heads"]


def bytes_moved(sizes: dict, s: int, itemsize: int = 2) -> float:
    hd = sizes["head_dim"]
    return itemsize * s * hd * (2 * sizes["heads"] + 2 * sizes["kv_heads"])
