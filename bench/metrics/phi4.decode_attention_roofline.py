"""``decode_attention_roofline`` in the phi4-batch cell: ``flash_attention``
inside the decode programs at query group 3 and head_dim 128, whose K/V
bytes ``bench/kernels/decode_attention.py`` counts by ``kv_heads``, read by
the same reader (``bench/metrics/decode_attention_roofline.py``)."""
from pathlib import Path

from bench.harness import spec

read = spec.load_module(
    Path(__file__).with_name("decode_attention_roofline.py")
).read
