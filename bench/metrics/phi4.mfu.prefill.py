"""``mfu.prefill`` in the phi4-batch cell: the prefill step's share of the
chip's peak at phi4-mini's widths (gated MLP, grouped-query attention, the
200k-row head), read by the same reader (``bench/metrics/mfu.prefill.py``)."""
from pathlib import Path

from bench.harness import spec

read = spec.load_module(Path(__file__).with_name("mfu.prefill.py")).read
