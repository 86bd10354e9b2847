"""The kernels' operation and byte counts on hand-computed shapes, and the
peaks table."""
from __future__ import annotations

import pytest

from bench.harness import spec

GPT2 = {"heads": 12, "kv_heads": 12, "head_dim": 64}
PHI4 = {"heads": 24, "kv_heads": 8, "head_dim": 128}


def test_causal_prefill_attention_at_s_300():
    k = spec.kernel("attention")
    # 300 * 301 / 2 = 45150 causal pairs; QK^T and PV, 2 flops each,
    # over head_dim 64 and 12 heads.
    assert k.flops(GPT2, 300) == 45150 * 2 * 2 * 64 * 12 == 138700800
    # q and out: 2 * 12 heads, k and v: 2 * 12 kv heads; 300 rows of 64, bf16.
    assert k.bytes_moved(GPT2, 300) == 2 * 300 * 64 * 48 == 1843200
    # GQA: k and v are read once per kv head, not once per query head.
    assert k.bytes_moved(PHI4, 300) == 2 * 300 * 128 * (48 + 16) == 4915200


def test_decode_attention_at_per_row_kv_len():
    k = spec.kernel("decode_attention")
    kv = (700, 411, 1)  # 1112 cache rows in all
    assert k.flops(PHI4, kv) == 1112 * 2 * 2 * 128 * 24 == 13664256
    # k and v: 8 kv heads x 1112 rows x 128; q and out: 24 heads x 3 rows.
    assert k.bytes_moved(PHI4, kv) == 2 * (2 * 8 * 128 * 1112
                                           + 2 * 24 * 128 * 3) == 4591616


def test_peaks_table():
    v5e = spec.peaks("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no peaks"):
        spec.peaks("TPU v9 imaginary")
