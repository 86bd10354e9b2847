"""Decode attention at per-row cache lengths: what the algorithm needs.

One call is one layer's decode attention for a batch whose active rows
hold ``kv_lens`` valid cache rows each (free slots need no work).  Per
row, each query head takes one QK^T and one PV against kv_len keys at
``head_dim``.  Bytes: each kv head's K and V rows read once, q and the
output once per query head, in the served dtype.
"""
from __future__ import annotations

# The program it runs in, and its op's name in the trace.
PROGRAM = "decode"
OP = "flash_attention"


def flops(sizes: dict, kv_lens) -> float:
    return 2 * 2 * sum(kv_lens) * sizes["head_dim"] * sizes["heads"]


def bytes_moved(sizes: dict, kv_lens, itemsize: int = 2) -> float:
    hd = sizes["head_dim"]
    kv = 2 * sizes["kv_heads"] * hd * sum(kv_lens)
    qo = 2 * sizes["heads"] * hd * len(kv_lens)
    return itemsize * (kv + qo)
