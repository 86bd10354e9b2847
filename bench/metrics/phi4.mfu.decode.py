"""``mfu.decode`` in the phi4-batch cell: the decode step's share of the
chip's peak at phi4-mini's widths, read by the same reader
(``bench/metrics/mfu.decode.py``)."""
from pathlib import Path

from bench.harness import spec

read = spec.load_module(Path(__file__).with_name("mfu.decode.py")).read
